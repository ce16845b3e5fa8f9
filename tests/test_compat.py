"""Compat-substrate tests: the wrappers forward the modern kwargs to the
installed JAX (checked with monkeypatched stand-ins), and the resolved
surface is checked against the really-installed JAX on a single-device
mesh."""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import compat


# --------------------------------------------------------------------------
# stand-ins for jax.shard_map / jax.make_mesh
# --------------------------------------------------------------------------

def _fake_shard_map(f, *, mesh, in_specs, out_specs, check_vma=True):
    return {"f": f, "mesh": mesh, "in_specs": in_specs,
            "out_specs": out_specs, "check": check_vma}


def test_shard_map_translates_check_kwarg(monkeypatch):
    monkeypatch.setattr(jax, "shard_map", _fake_shard_map)
    fn = lambda x: x
    out = compat.shard_map(fn, mesh="MESH", in_specs=P(), out_specs=P(),
                           check_vma=False)
    # check_vma=False must reach the impl
    assert out["check"] is False
    assert out["f"] is fn and out["mesh"] == "MESH"


def test_shard_map_default_check_left_alone(monkeypatch):
    monkeypatch.setattr(jax, "shard_map", _fake_shard_map)
    out = compat.shard_map(lambda x: x, mesh="M", in_specs=P(), out_specs=P())
    assert out["check"] is True   # impl default, untouched


def _fake_make_mesh(axis_shapes, axis_names, axis_types=None, *,
                      devices=None):
    return {"shapes": tuple(axis_shapes), "names": tuple(axis_names),
            "devices": devices, "axis_types": axis_types}


def test_make_mesh_modern_gets_auto_axis_types(monkeypatch):
    monkeypatch.setattr(jax, "make_mesh", _fake_make_mesh)
    monkeypatch.setattr(compat, "axis_type_auto", lambda: "AUTO")
    out = compat.make_mesh((2, 4), ("data", "model"))
    assert out["axis_types"] == ("AUTO", "AUTO")
    assert out["shapes"] == (2, 4) and out["names"] == ("data", "model")


# --------------------------------------------------------------------------
# against the really-installed JAX
# --------------------------------------------------------------------------

def test_make_mesh_real_jax_single_device():
    m = compat.make_mesh((1,), ("data",))
    assert m.axis_names == ("data",)
    assert m.devices.shape == (1,)


def test_shard_map_real_jax_executes():
    mesh = compat.make_mesh((1,), ("x",))
    fn = compat.shard_map(lambda a: a * 2.0, mesh=mesh, in_specs=P(),
                          out_specs=P(), check_vma=False)
    out = jax.jit(fn)(jnp.arange(4.0))
    np.testing.assert_allclose(np.asarray(out), np.arange(4.0) * 2.0)


def test_cost_analysis_normalizes_all_shapes():
    class FakeCompiled:
        def __init__(self, ret):
            self._ret = ret

        def cost_analysis(self):
            return self._ret

    # a dict, or None on backends without a cost model
    assert compat.cost_analysis(FakeCompiled({"flops": 7.0})) == {"flops": 7.0}
    assert compat.cost_analysis(FakeCompiled(None)) == {}


def test_cost_analysis_real_jax():
    compiled = jax.jit(lambda x: x @ x).lower(jnp.ones((8, 8))).compile()
    cost = compat.cost_analysis(compiled)
    assert isinstance(cost, dict)
    assert float(cost.get("flops", 0.0)) > 0


def test_axis_size_static_inside_shard_map():
    mesh = compat.make_mesh((1,), ("x",))

    def body(a):
        d = compat.axis_size("x")
        assert int(d) == 1          # must be usable at trace time
        return a * d

    fn = compat.shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(),
                          check_vma=False)
    out = jax.jit(fn)(jnp.arange(3.0))
    np.testing.assert_allclose(np.asarray(out), np.arange(3.0))


def test_tree_shim_roundtrip():
    t = {"a": jnp.ones((2,)), "b": [jnp.zeros((1,)), jnp.ones(())]}
    leaves, tdef = compat.tree.flatten(t)
    assert len(leaves) == len(compat.tree.leaves(t)) == 3
    t2 = compat.tree.unflatten(tdef, leaves)
    doubled = compat.tree.map(lambda x: x * 2, t)
    assert float(doubled["a"][0]) == 2.0
    assert compat.tree.structure(t2) == compat.tree.structure(t)


def test_tree_shim_map_with_path():
    t = {"a": jnp.ones((2,)), "b": jnp.zeros(())}
    keyed = compat.tree.map_with_path(
        lambda path, x: float(x.sum()) + len(path), t)
    assert keyed == {"a": 3.0, "b": 1.0}
