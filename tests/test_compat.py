"""Unit tests for the JAX compat layer (repro.compat).

Each surface runs on the installed JAX, and its argument translation is
checked against a fake installed by monkeypatching the module-level
attribute the shim resolves at call time.
"""
import enum
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat


# ---------------------------------------------------------------------------
# make_mesh / AxisType
# ---------------------------------------------------------------------------

def test_make_mesh_builds_on_installed_jax():
    mesh = compat.make_mesh((1,), ("data",))
    assert mesh.axis_names == ("data",)
    assert mesh.shape["data"] == 1


def test_make_mesh_passes_axis_types_when_supported(monkeypatch):
    """Simulate a new JAX: AxisType exists and make_mesh accepts axis_types."""
    class FakeAxisType(enum.Enum):
        Auto = "auto"
        Explicit = "explicit"

    calls = {}

    def fake_make_mesh(axis_shapes, axis_names, *, devices=None,
                       axis_types=None):
        calls["args"] = (axis_shapes, axis_names, devices, axis_types)
        return "mesh"

    monkeypatch.setattr(compat, "AxisType", FakeAxisType)
    monkeypatch.setattr(compat, "_JAX_MAKE_MESH", fake_make_mesh)
    out = compat.make_mesh((2, 4), ("data", "model"), axis_types="auto")
    assert out == "mesh"
    assert calls["args"] == ((2, 4), ("data", "model"), None,
                             (FakeAxisType.Auto, FakeAxisType.Auto))


def test_make_mesh_rejects_bogus_axis_types():
    """Validation must not depend on which JAX branch is installed."""
    with pytest.raises(ValueError, match="axis_types"):
        compat.make_mesh((1,), ("data",), axis_types="bogus")


# ---------------------------------------------------------------------------
# shard_map
# ---------------------------------------------------------------------------

def test_shard_map_runs_on_installed_jax():
    mesh = compat.make_mesh((1,), ("data",))
    x = jnp.arange(4.0)
    out = compat.shard_map(
        lambda v: v * 2, mesh=mesh,
        in_specs=jax.sharding.PartitionSpec(),
        out_specs=jax.sharding.PartitionSpec(),
        check_vma=False)(x)
    np.testing.assert_allclose(out, np.arange(4.0) * 2)


def test_shard_map_new_api_translation(monkeypatch):
    """axis_names/check_vma pass straight through to a new-style jax.shard_map."""
    calls = {}

    def fake_new(f, *, mesh, in_specs, out_specs, check_vma, axis_names=None):
        calls["kw"] = dict(mesh=mesh, check_vma=check_vma,
                           axis_names=axis_names)
        return f

    monkeypatch.setattr(compat, "_NEW_SHARD_MAP", fake_new)
    fn = lambda v: v
    out = compat.shard_map(fn, mesh="m", in_specs=0, out_specs=0,
                           axis_names={"model"}, check_vma=False)
    assert out is fn
    assert calls["kw"] == {"mesh": "m", "check_vma": False,
                           "axis_names": {"model"}}
    # axis_names=None must omit the kwarg (new API default = all manual)
    compat.shard_map(fn, mesh="m", in_specs=0, out_specs=0, check_vma=True)
    assert calls["kw"]["axis_names"] is None
    assert calls["kw"]["check_vma"] is True


# ---------------------------------------------------------------------------
# pallas compiler params / pallas_call
# ---------------------------------------------------------------------------

def test_tpu_compiler_params_resolves_installed_name():
    params = compat.tpu_compiler_params(
        dimension_semantics=("parallel", "arbitrary"))
    assert tuple(params.dimension_semantics) == ("parallel", "arbitrary")


def test_tpu_compiler_params_new_name(monkeypatch):
    class FakeParams:
        def __init__(self, dimension_semantics=None):
            self.dimension_semantics = dimension_semantics

    fake = types.SimpleNamespace(CompilerParams=FakeParams)
    monkeypatch.setattr(compat, "_pltpu", fake)
    p = compat.tpu_compiler_params(dimension_semantics=("parallel",))
    assert isinstance(p, FakeParams)
    assert p.dimension_semantics == ("parallel",)
    # a misspelt parameter is an error, never silently dropped
    with pytest.raises(TypeError):
        compat.tpu_compiler_params(dimension_semantics=("parallel",),
                                   bogus_kwarg=1)


def test_pallas_call_degrades_to_interpret_off_tpu(monkeypatch):
    calls = {}

    def fake_pallas_call(kernel, *, interpret, **kwargs):
        calls["interpret"] = interpret
        return kernel

    monkeypatch.setattr(compat, "_PALLAS_CALL", fake_pallas_call)
    monkeypatch.setattr(compat, "_backend", lambda: "cpu")
    compat.pallas_call(lambda: None, out_shape=None)
    assert calls["interpret"] is True


def test_pallas_call_compiles_on_tpu(monkeypatch):
    calls = {}

    def fake_pallas_call(kernel, *, interpret, **kwargs):
        calls["interpret"] = interpret
        return kernel

    monkeypatch.setattr(compat, "_PALLAS_CALL", fake_pallas_call)
    monkeypatch.setattr(compat, "_backend", lambda: "tpu")
    compat.pallas_call(lambda: None, out_shape=None)
    assert calls["interpret"] is False
    # explicit interpret=True is preserved even on TPU
    compat.pallas_call(lambda: None, out_shape=None, interpret=True)
    assert calls["interpret"] is True


def test_pallas_call_refuses_other_backends(monkeypatch):
    """Off CPU and TPU nothing falls back to the interpreter by itself."""
    calls = {}

    def fake_pallas_call(kernel, *, interpret, **kwargs):
        calls["interpret"] = interpret
        return kernel

    monkeypatch.setattr(compat, "_PALLAS_CALL", fake_pallas_call)
    monkeypatch.setattr(compat, "_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        compat.interpret_default()
    with pytest.raises(RuntimeError, match="gpu"):
        compat.pallas_call(lambda: None, out_shape=None)
    assert "interpret" not in calls
    # asking for the interpreter explicitly is still allowed
    compat.pallas_call(lambda: None, out_shape=None, interpret=True)
    assert calls["interpret"] is True
