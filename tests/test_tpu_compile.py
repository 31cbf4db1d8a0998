"""Compile-only rehearsal of the Pallas kernels for a TPU v5e.

Nothing runs on a chip here: each test lowers a kernel for a v5e described
by ``jax.experimental.topologies`` and compiles it with the TPU compiler
installed alongside JAX.  That refuses what the interpreter accepts and
Mosaic cannot lower — a block off the (8, 128) tiling, a matmul without a
non-contracting dimension, more scoped VMEM than a kernel may use — before
any chip time is spent.  Widths are the real ones: F = 128 lanes, f = 100
through ``ops.als_update_factor`` for the padding path, and the batched
solve at its unpadded f = 100 and f = 10 through ``ops.batch_solve``.
The accumulate-Theta programs compile at the SparkALS share's real sizes
(n = 2.4M items, f = 10), and their arguments and temporaries must fit
one chip's 16 GB: the out-of-memory failure of that cell, caught here;
so does a solve-X wave through the same programs at both cells' sizes.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import compat
from repro.core import als as als_mod
from repro.core.partition import lanes_bytes
from repro.kernels import ops
from repro.kernels.hermitian import fused_herm_pallas, herm_hbm_accum
from repro.kernels.sgd_update import sgd_tile_pallas

F = 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_compile(monkeypatch, one_chip):
    """compile(fn, *(shape, dtype)) for one described v5e chip.

    The kernels' ``compat.pallas_call`` is steered to compile for the TPU,
    and the persistent compilation cache is off: an entry written for a
    described chip cannot be read back without one.
    """
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(compat, "_backend", lambda: "tpu")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # the ops' jitted wrappers pick a path by backend while tracing: no
    # trace cached under the CPU backend may serve here, nor one of these
    # after the test
    jax.clear_caches()

    def compile_(fn, *shapes, kernel=True):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        compiled = jax.jit(fn).lower(*args).compile()
        assert kernel == ("tpu_custom_call" in compiled.as_text())
        return compiled

    try:
        yield compile_
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()
        jax.clear_caches()


f32, i32 = jnp.float32, jnp.int32


def test_fused_herm_compiles_for_v5e(tpu_compile):
    m, K = 64, 256
    tpu_compile(fused_herm_pallas, ((m, K, F), f32), ((m, K), f32),
                ((m, K), f32), ((m,), f32))


def test_herm_hbm_accum_compiles_for_v5e(tpu_compile):
    m, K = 64, 256
    tpu_compile(herm_hbm_accum, ((m, K, F), f32), ((m, K), f32),
                ((m, K), f32), ((m,), f32))


@pytest.mark.parametrize("f,m", [(100, 16384), (100, 15200), (10, 16384)])
def test_batch_solve_compiles_for_v5e(tpu_compile, f, m):
    """The systems-minor solve at a full row block, a ragged bin, and the
    SparkALS width: the kernel replaces XLA's Cholesky custom calls."""
    compiled = tpu_compile(ops.batch_solve, ((m, f, f), f32), ((m, f), f32))
    text = compiled.as_text()
    assert "Cholesky" not in text and "InvertDiagBlocks" not in text


def test_sgd_tile_compiles_for_v5e(tpu_compile):
    mb = nb = 1024       # the budget's worst-case tile
    K = 32
    tpu_compile(lambda x, t, i, v, k, lr: sgd_tile_pallas(
                    x, t, i, v, k, lr, lam=0.05),
                ((mb, F), f32), ((nb, F), f32), ((mb, K), i32),
                ((mb, K), f32), ((mb, K), f32), ((1, 1), f32))


def test_als_update_factor_kernel_mode_compiles_at_f100(tpu_compile):
    n, m, K, f = 1000, 300, 40, 100     # pads m, K and f to the tiles
    tpu_compile(lambda th, i, v, c: ops.als_update_factor(
                    th, i, v, c, 0.05, mode="kernel"),
                ((n, f), f32), ((m, K), i32), ((m, K), f32), ((m,), i32))


HBM = 16e9
#: the SparkALS share (chipbench/configs/sparkals_f10.json)
SPARK_M, SPARK_N = 1_885_714, 2_400_000


def _fits_one_chip(compiled):
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < HBM, (used, mem)
    return used


@pytest.mark.parametrize("scatter", [False, True], ids=["run", "scatter"])
def test_accumulate_bin_fits_v5e(tpu_compile, scatter):
    """One bin's update at n = 2.4M, f = 10, K = 8 in 16,384-row blocks:
    the gather and the partials are one block's.  Into a run of columns
    the accumulators are updated in place, in their own layout (no relaid
    copy of them); scattered, the one copy is the columns-major
    ``[n, f*f]`` one the scatter-add works in (1.23 GB; ``[f, f, n]``
    would be relaid as ``[n, f, f]``, 19.7 GB)."""
    f, K, block = 10, 8, 16384
    rows = 139 * block                   # the first item bin, ~2.27M rows
    col = (((rows,), i32),) if scatter else (((), i32),)
    compiled = tpu_compile(
        lambda A, B, c, x, i, v, k, j: als_mod._accumulate_bin(
            A, B, c, x, i, v, k, j if scatter else None,
            0 if scatter else j, lam=0.05, block=block, mode="ref", tm=8,
            tk=128, f_mult=128),
        ((f * f, SPARK_N), f32), ((f, SPARK_N), f32), ((SPARK_N,), f32),
        ((SPARK_M, f), f32), ((K, rows), i32), ((K, rows), f32),
        ((rows,), i32), *col, kernel=False)
    copy = lanes_bytes((SPARK_N, f * f)) if scatter else 0
    assert compiled.memory_analysis().temp_size_in_bytes < copy + (256 << 20)
    assert _fits_one_chip(compiled) < 3e9


@pytest.mark.parametrize("f,n", [(10, SPARK_N), (100, 17770)])
def test_solve_accumulated_fits_v5e(tpu_compile, f, n):
    """The lanes solve of an accumulator as it lies, at the SparkALS and
    Netflix item counts: the kernel, with no Cholesky custom call."""
    compiled = tpu_compile(
        lambda A, B, c: als_mod._solve_accumulated(A, B, c, mode="ref"),
        ((f * f, n), f32), ((f, n), f32), ((n,), f32))
    assert "Cholesky" not in compiled.as_text()
    _fits_one_chip(compiled)


@pytest.mark.parametrize("f,n,m,width,K,rows,block", [
    (10, SPARK_N, SPARK_M, 1_922_048, 8, 1_638_400, 16_384),
    (100, 17_770, 48_504, 49_152, 616, 8_832, 8_832)],
    ids=["sparkals", "netflix"])
def test_x_wave_fits_v5e(tpu_compile, f, n, m, width, K, rows, block):
    """A solve-X wave through the lanes programs, as one program, at the
    cells' user layouts (``acc_layout`` widths of their generated users,
    seed 3000018001): zero accumulators, the update of the bin with the
    most slots (SparkALS) or the largest gather (Netflix), the lanes
    solve and the take back into user order fit one chip."""

    def wave(theta, idx, val, cnt, col_of):
        lb = als_mod.LanesBin(idx=idx, val=val, cnt=cnt, cols=None,
                              start=0, block=block, rows=rows, nnz=0)
        cfg = als_mod.AlsConfig(f=f, lam=0.05, batch_rows=16_384)
        return als_mod.solve_rows_lanes(theta, [lb], col_of, width, cfg)

    compiled = tpu_compile(wave, ((n, f), f32), ((K, rows), i32),
                           ((K, rows), f32), ((rows,), i32), ((m,), i32))
    assert "Cholesky" not in compiled.as_text()
    assert _fits_one_chip(compiled) < 8e9
