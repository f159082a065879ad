"""repro.compat unit tests: each forward reaches the JAX API it wraps (the
API is monkeypatched to observe the call) and runs for real."""
import contextlib
import enum

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro import compat


class _FakeAxisType(enum.Enum):
    Auto = "auto"
    Explicit = "explicit"


# ---------------------------------------------------------------------------
# make_mesh
# ---------------------------------------------------------------------------

def test_make_mesh_new_api_branch(monkeypatch):
    """make_mesh forwards all-auto axis types to jax.make_mesh."""
    calls = {}

    def fake_make_mesh(axis_shapes, axis_names, *, axis_types=None, devices=None):
        calls["args"] = (axis_shapes, axis_names, axis_types, devices)
        return "fake-mesh"

    monkeypatch.setattr(jax.sharding, "AxisType", _FakeAxisType, raising=False)
    monkeypatch.setattr(jax, "make_mesh", fake_make_mesh)
    out = compat.make_mesh((1, 1), ("data", "model"))
    assert out == "fake-mesh"
    shapes, names, types, _ = calls["args"]
    assert shapes == (1, 1) and names == ("data", "model")
    assert types == (_FakeAxisType.Auto, _FakeAxisType.Auto)


def test_make_mesh_on_given_devices():
    """An explicit device list builds the mesh on exactly those devices."""
    devs = jax.devices()[:1]
    mesh = compat.make_mesh((1, 1), ("data", "model"), devices=devs)
    assert mesh.axis_names == ("data", "model")
    assert list(mesh.devices.flat) == devs


def test_make_mesh_shape_mismatch_raises():
    with pytest.raises(ValueError):
        compat.make_mesh((1, 1), ("data",))


# ---------------------------------------------------------------------------
# set_mesh / current_mesh
# ---------------------------------------------------------------------------

def test_set_mesh_prefers_jax_set_mesh(monkeypatch):
    seen = {}

    @contextlib.contextmanager
    def fake_set_mesh(mesh):
        seen["mesh"] = mesh
        yield mesh

    monkeypatch.setattr(jax, "set_mesh", fake_set_mesh, raising=False)
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    with compat.set_mesh(mesh):
        pass
    assert seen["mesh"] is mesh


def test_current_mesh_prefers_get_abstract_mesh(monkeypatch):
    class FakeMesh:
        empty = False
        axis_names = ("a",)
        axis_sizes = (4,)

    monkeypatch.setattr(
        jax.sharding, "get_abstract_mesh", lambda: FakeMesh(), raising=False
    )
    assert compat.current_mesh_axis_sizes() == {"a": 4}
    # empty abstract mesh -> None, never an exception
    FakeMesh.empty = True
    assert compat.current_mesh() is None
    monkeypatch.setattr(jax.sharding, "get_abstract_mesh", lambda: None, raising=False)
    assert compat.current_mesh() is None


# ---------------------------------------------------------------------------
# shard_map
# ---------------------------------------------------------------------------

def test_shard_map_new_api_branch(monkeypatch):
    calls = {}

    def fake_shard_map(f, *, mesh, in_specs, out_specs, axis_names, check_vma):
        calls.update(axis_names=axis_names, check_vma=check_vma)
        return f

    monkeypatch.setattr(jax, "shard_map", fake_shard_map, raising=False)
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    fn = compat.shard_map(
        lambda x: x, mesh=mesh, in_specs=P(), out_specs=P(),
        axis_names={"data"}, check_vma=False,
    )
    assert fn(3) == 3
    assert calls["axis_names"] == {"data"} and calls["check_vma"] is False


def test_shard_map_executes_on_this_pin():
    """No monkeypatching: the forward must actually run."""
    mesh = compat.make_mesh((1,), ("d",))
    fn = compat.shard_map(
        lambda x: jax.lax.psum(x, "d"), mesh=mesh,
        in_specs=P("d"), out_specs=P(), axis_names={"d"}, check_vma=False,
    )
    out = jax.jit(fn)(jnp.ones((1, 3)))
    assert out.shape == (1, 3)


def test_shard_map_unknown_axis_raises():
    mesh = compat.make_mesh((1,), ("d",))
    with pytest.raises(ValueError):
        compat.shard_map(
            lambda x: x, mesh=mesh, in_specs=P(), out_specs=P(),
            axis_names={"nope"},
        )


# ---------------------------------------------------------------------------
# normalized_cost_analysis
# ---------------------------------------------------------------------------

class _FakeCompiled:
    def __init__(self, result):
        self._result = result

    def cost_analysis(self):
        return self._result


def test_cost_analysis_dict_passthrough():
    d = {"flops": 10.0, "bytes accessed": 4.0}
    out = compat.normalized_cost_analysis(_FakeCompiled(d))
    assert out == d
    assert out is not d  # defensive copy


def test_cost_analysis_none_and_empty():
    assert compat.normalized_cost_analysis(_FakeCompiled(None)) == {}
    assert compat.normalized_cost_analysis(_FakeCompiled({})) == {}


def test_cost_analysis_real_compiled_is_dict():
    comp = jax.jit(lambda a: a * 2).lower(
        jax.ShapeDtypeStruct((8, 8), jnp.float32)
    ).compile()
    out = compat.normalized_cost_analysis(comp)
    assert isinstance(out, dict)
    assert "bytes accessed" in out
