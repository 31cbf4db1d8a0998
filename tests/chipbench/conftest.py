"""CPU sizes of the configurations added after ``chipbench_tiny``: each
keeps its widths f and lambda and its shape (users, items and ratings in
the published ratios), cut to seconds."""
import chipbench_tiny

chipbench_tiny.TINY.setdefault(
    "sparkals_f10", {"m": 1500, "n": 1920, "nnz": 8000})
