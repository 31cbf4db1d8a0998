"""One home for the JAX surfaces whose spelling is version-sensitive.

The supported JAX is the one the TPU image carries (0.9.0, see
``pyproject.toml``).  Everything else imports these names from here, so the
next API drift is a one-file fix plus a green import-sweep test — not a
call-site hunt.

- :func:`make_mesh`: ``jax.make_mesh`` with ``axis_types`` spelled as
  ``"auto"`` / ``"explicit"`` / ``None``.
- :func:`shard_map`: ``jax.shard_map`` (``axis_names=`` = the manual axes,
  ``check_vma=``).
- :func:`tpu_compiler_params` / :func:`vmem`: ``pltpu.CompilerParams`` and
  ``pltpu.VMEM``.
- :func:`pallas_call`: ``pl.pallas_call`` that compiles on TPU, runs the
  Pallas interpreter on the CPU backend (where the tests run), and refuses
  every other backend rather than silently interpreting there;
  :func:`on_tpu` for code that picks a kernel by backend.

Resolution goes through module-level attributes looked up at call time, so
tests can monkeypatch them.
"""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as _pltpu
from jax.sharding import AxisType

_NEW_SHARD_MAP = jax.shard_map
_JAX_MAKE_MESH = jax.make_mesh
_PALLAS_CALL = pl.pallas_call


# ---------------------------------------------------------------------------
# Mesh construction
# ---------------------------------------------------------------------------

def make_mesh(axis_shapes, axis_names, *, devices=None, axis_types="auto"):
    """Build a ``Mesh``; ``axis_types`` is ``"auto"`` / ``"explicit"`` /
    ``None`` (None leaves the choice to ``jax.make_mesh``)."""
    if axis_types not in (None, "auto", "explicit"):
        raise ValueError(
            f"axis_types must be 'auto', 'explicit', or None; got "
            f"{axis_types!r}")
    axis_shapes = tuple(axis_shapes)
    axis_names = tuple(axis_names)
    if axis_types is None:
        return _JAX_MAKE_MESH(axis_shapes, axis_names, devices=devices)
    kind = {"auto": AxisType.Auto, "explicit": AxisType.Explicit}[axis_types]
    return _JAX_MAKE_MESH(axis_shapes, axis_names, devices=devices,
                          axis_types=(kind,) * len(axis_names))


# ---------------------------------------------------------------------------
# shard_map
# ---------------------------------------------------------------------------

def shard_map(f, *, mesh, in_specs, out_specs, axis_names=None,
              check_vma=False):
    """``jax.shard_map``; ``axis_names``: mesh axes to run manually (None =
    all of them, the API's default)."""
    kwargs = {}
    if axis_names is not None:
        kwargs["axis_names"] = set(axis_names)
    return _NEW_SHARD_MAP(f, mesh=mesh, in_specs=in_specs,
                          out_specs=out_specs, check_vma=check_vma, **kwargs)


# ---------------------------------------------------------------------------
# Pallas
# ---------------------------------------------------------------------------

def tpu_compiler_params(**kwargs):
    """``pltpu.CompilerParams(**kwargs)``."""
    return _pltpu.CompilerParams(**kwargs)


def vmem(shape, dtype):
    """A VMEM scratch allocation (``pltpu.VMEM(shape, dtype)``)."""
    return _pltpu.VMEM(tuple(shape), dtype)


def _backend() -> str:
    return jax.default_backend()


def on_tpu() -> bool:
    """Whether JAX's default backend is a TPU."""
    return _backend() == "tpu"


def interpret_default() -> bool:
    """Whether Pallas kernels run interpreted by default on this backend.

    False on TPU, where Mosaic compiles them; True on the CPU backend, the
    test host.  Any other backend raises: the kernels are written for the
    TPU, and interpreting them elsewhere would hide that the device path
    never ran.
    """
    backend = _backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels compile only for TPU and are interpreted only on "
        f"CPU; backend {backend!r} is neither (pass interpret=True to "
        f"interpret explicitly)")


def pallas_call(kernel, *, interpret=False, **kwargs):
    """``pl.pallas_call``: compiled on TPU, interpreted on CPU or when the
    caller asks for it (see :func:`interpret_default`)."""
    if not interpret:
        interpret = interpret_default()
    return _PALLAS_CALL(kernel, interpret=interpret, **kwargs)
