"""Compile-only rehearsal of the Pallas kernels for a TPU v5e.

Nothing runs on a chip here: each test lowers a kernel for a v5e described
by ``jax.experimental.topologies`` and compiles it with the TPU compiler
installed alongside JAX.  That refuses what the interpreter accepts and
Mosaic cannot lower — a block off the (8, 128) tiling, a matmul without a
non-contracting dimension, more scoped VMEM than a kernel may use — before
any chip time is spent.  Widths are the real ones: F = 128 lanes, and
f = 100 through ``ops.als_update_factor`` for the padding path.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import compat
from repro.kernels import ops
from repro.kernels.batch_solve import batch_solve_pallas
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

    def compile_(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        compiled = jax.jit(fn).lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text()
        return compiled

    try:
        yield compile_
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()


f32, i32 = jnp.float32, jnp.int32


def test_fused_herm_compiles_for_v5e(tpu_compile):
    m, K = 64, 256
    tpu_compile(fused_herm_pallas, ((m, K, F), f32), ((m, K), f32),
                ((m, K), f32), ((m,), f32))


def test_herm_hbm_accum_compiles_for_v5e(tpu_compile):
    m, K = 64, 256
    tpu_compile(herm_hbm_accum, ((m, K, F), f32), ((m, K), f32),
                ((m, K), f32), ((m,), f32))


def test_batch_solve_compiles_for_v5e(tpu_compile):
    m = 64
    tpu_compile(batch_solve_pallas, ((m, F, F), f32), ((m, F), f32))


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
