"""Share of the chip's bf16 peak that the useful flops of the window's
iterations reach, in %: ``chipbench.flops.als_iteration_flops`` times
iterations over the window's length, over ``peaks.json``'s ``bf16_flops``.
Nothing off the chip (no peak)."""


def read(w):
    if not w.peak_flops:
        return None
    return 100.0 * w.flops_per_iter * w.iterations / w.seconds / w.peak_flops
