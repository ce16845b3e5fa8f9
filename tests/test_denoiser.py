"""Denoiser seam unit tests: adapter semantics, mesh-requirement errors,
and the spec composition in parallel.sharding (single-device — the
multi-device numerics live in test_distributed_srds.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.compat import make_mesh
from repro.core.denoiser import Denoiser, as_denoiser
from repro.parallel.sharding import denoiser_spec, microbatch_spec


def _fn(x, t):
    return x * t


def _apply(params, x, t):
    return x * t


def _shard_apply(params, x, t):
    return x * t


MESH3 = lambda: make_mesh((1, 1, 1), ("time", "data", "model"))


# ------------------------------------------------------------ adapter

def test_as_denoiser_adapts_plain_fn_and_is_identity_on_denoisers():
    den = as_denoiser(_fn)
    assert isinstance(den, Denoiser)
    assert not den.is_model_parallel
    assert as_denoiser(den) is den
    x = jnp.arange(4.0)
    assert jnp.array_equal(den(x, 2.0), _fn(x, 2.0))
    # plain denoisers short-circuit every composition mode to fn itself
    assert den.inner_eval() is _fn
    assert den.shard_eval() is _fn


def _scale_apply(params, x, t):
    return x * params["w"] * t


def test_denoiser_closes_fn_over_the_params_it_carries():
    w = {"w": jnp.float32(3.0)}
    den = Denoiser(apply=_scale_apply, params=w)
    assert as_denoiser(_fn).params == ()
    x = jnp.arange(4.0)
    assert jnp.array_equal(den(x, 2.0), x * 6.0)
    assert jnp.array_equal(den.fn(x, 2.0), x * 6.0)
    assert jnp.array_equal(den.inner_eval()(x, 2.0), x * 6.0)
    assert den.shard_fn is None
    # the same seam evaluates other weights of the same shapes
    other = den.with_params({"w": jnp.float32(0.5)})
    assert jnp.array_equal(other(x, 2.0), x)
    assert den.params is w and other.apply is den.apply


@pytest.mark.parametrize("shard_axis", [None, "model"])
def test_dit_denoiser_carries_its_weights(shard_axis):
    """``make_denoiser`` returns a weight-carrying Denoiser with or
    without ``shard_axis``; standalone, its calls pass the weights into
    the shard_map as an argument and match ``apply``."""
    import dataclasses

    from repro.configs.base import get_arch
    from repro.configs.srds_dit import dit_denoiser
    from repro.models.dit import init_dit
    cfg = dataclasses.replace(get_arch("srds-dit-cifar"), num_layers=1,
                              d_model=32, num_heads=2, num_kv_heads=2,
                              d_ff=64, dtype="float32")
    params = init_dit(cfg, jax.random.PRNGKey(0))
    den = dit_denoiser(cfg, params, use_kernel=False, shard_axis=shard_axis,
                       mesh=shard_axis and MESH3())
    assert isinstance(den, Denoiser) and den.params is params
    assert den.is_model_parallel == (shard_axis is not None)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 8, 3))
    want = den.apply(params, x, 0.5)
    np.testing.assert_allclose(jax.jit(den)(x, 0.5), want, rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_allclose(den.fn(x, 0.5), want, rtol=0, atol=0)


def test_denoiser_with_mesh_axes_requires_shard_fn():
    with pytest.raises(ValueError, match="needs a shard_fn"):
        Denoiser(apply=_apply, mesh_axes={"model": 2})


def test_standalone_model_parallel_call_requires_bound_mesh():
    den = Denoiser(apply=_apply, shard_apply=_shard_apply,
                   in_spec=P(None, "model"), out_spec=P(None, "model"),
                   mesh_axes={"model": 1})
    with pytest.raises(ValueError, match="bound"):
        den(jnp.ones((2, 2)), 0.5)


# ------------------------------------------------------- mesh validation

def test_check_mesh_names_the_missing_axis():
    den = Denoiser(apply=_apply, shard_apply=_shard_apply,
                   in_spec=P(None, "model"), out_spec=P(None, "model"),
                   mesh_axes={"model": 1})
    mesh = make_mesh((1,), ("time",))
    with pytest.raises(ValueError, match=r"mesh axis 'model'.*\('time',\)"):
        den.check_mesh(mesh)


def test_check_mesh_enforces_min_size():
    den = Denoiser(apply=_apply, shard_apply=_shard_apply,
                   in_spec=P(None, "model"), out_spec=P(None, "model"),
                   mesh_axes={"model": 2})
    with pytest.raises(ValueError, match="size >= 2"):
        den.check_mesh(MESH3())
    with pytest.raises(ValueError, match="size >= 2"):
        den.bind(MESH3())       # binding validates too


# ------------------------------------------- spec composition + validation

def test_microbatch_spec_validates_axis_is_bound():
    assert microbatch_spec("data", mesh=MESH3()) == P(None, "data")
    with pytest.raises(ValueError, match=r"'dp' is not bound.*'time', "
                                         r"'data', 'model'"):
        microbatch_spec("dp", mesh=MESH3())


def test_denoiser_spec_composes_data_and_model_axes():
    den = Denoiser(apply=_apply, shard_apply=_shard_apply,
                   in_spec=P(None, "model"), out_spec=P(None, "model"),
                   mesh_axes={"model": 1})
    # sample layout (K, H, W, C): in_spec's K entry drops, H shifts onto
    # the heads tensor's dim 2 -> (B, K, H, ...) = (None, data, model)
    assert denoiser_spec("data", den, mesh=MESH3()) == P(None, "data",
                                                         "model")
    # degraded forms: plain fn / no denoiser == microbatch_spec
    assert denoiser_spec("data", _fn) == P(None, "data")
    assert denoiser_spec("data") == P(None, "data")
    assert denoiser_spec(None, den) == P(None, None, "model")


def test_denoiser_spec_rejects_sample_batch_sharding_and_unbound_axes():
    den_bad = Denoiser(apply=_apply, shard_apply=_shard_apply,
                       in_spec=P("model",), out_spec=P("model",),
                       mesh_axes={"model": 1})
    with pytest.raises(ValueError, match="owns that dim via data_axis"):
        denoiser_spec("data", den_bad)
    den = Denoiser(apply=_apply, shard_apply=_shard_apply,
                   in_spec=P(None, "mp"),
                   out_spec=P(None, "mp"), mesh_axes={"mp": 1})
    with pytest.raises(ValueError, match="mesh axis 'mp'"):
        denoiser_spec("data", den, mesh=MESH3())


# -------------------------------------------------- engine entry validation

def test_serving_engine_rejects_unbound_data_axis_and_meshless_mp():
    from repro.serve.diffusion import DiffusionSamplingEngine
    with pytest.raises(ValueError, match="'dp' is not bound"):
        DiffusionSamplingEngine(_fn, (4,), num_steps=8, batch_size=1,
                                mesh=MESH3(), data_axis="dp")
    den = Denoiser(apply=_apply, shard_apply=_shard_apply,
                   in_spec=P(None, "model"), out_spec=P(None, "model"),
                   mesh_axes={"model": 1})
    with pytest.raises(ValueError, match="needs a mesh"):
        DiffusionSamplingEngine(den, (2, 2), num_steps=8, batch_size=1)


def test_serving_engine_rejects_lane_sharding_denoiser():
    # the engine owns the lane dim (data_axis): a denoiser whose in_spec
    # shards it cannot be composed into the fine solves' heads spec
    den = Denoiser(apply=_apply, shard_apply=_shard_apply,
                   in_spec=P("model",),
                   out_spec=P("model",), mesh_axes={"model": 1})
    from repro.serve.diffusion import DiffusionSamplingEngine
    with pytest.raises(ValueError, match="owns that dim via data_axis"):
        DiffusionSamplingEngine(den, (2, 2), num_steps=8, batch_size=1,
                                mesh=MESH3(), axis="time", data_axis="data")


def _smooth(x, t):
    return jnp.tanh(x) * (0.5 + 0.001 * t)


def test_serving_engine_composes_model_parallel_denoiser_on_mesh():
    """A model-parallel denoiser whose shard_fn needs its ``model`` axis
    bound (a collective) runs in the engine's mesh programs — the coarse
    sweep through ``inner_eval``, the fine solves through ``shard_eval`` —
    and serves the samples of the plain function without a mesh, up to
    float roundoff: the collective ends an XLA fusion, so the solver's
    multiply-adds round apart in the last bits."""
    from repro.serve.diffusion import DiffusionSamplingEngine, SampleRequest

    def apply(params, x, t):
        return _smooth(x, t)

    def shard_apply(params, x, t):
        return jax.lax.psum(_smooth(x, t), "model")   # size-1 axis: identity

    den = Denoiser(apply=apply, shard_apply=shard_apply,
                   in_spec=P(None, "model"), out_spec=P(None, "model"),
                   mesh_axes={"model": 1})
    reqs = [SampleRequest(seed=s, tol=1e-3) for s in range(3)]
    out = []
    for model, kw in ((den, dict(mesh=MESH3(), axis="time",
                                 data_axis="data")),
                      # the block axis runs FixedBudget: match it
                      (_smooth, dict(truncate=False))):
        eng = DiffusionSamplingEngine(model, (4, 2), num_steps=16,
                                      batch_size=2, **kw)
        rids = [eng.submit(r) for r in reqs]
        got = eng.drain()
        out.append([(got[i].sample, got[i].iterations) for i in rids])
    for (a, ia), (b, ib) in zip(*out):
        assert ia == ib
        np.testing.assert_allclose(a, b, rtol=2e-6, atol=0)


def test_sharded_driver_rejects_mesh_missing_model_axis():
    from repro.core import SRDSConfig, SolverConfig, make_schedule
    from repro.core.pipelined import make_sharded_sampler
    den = Denoiser(apply=_apply, shard_apply=_shard_apply,
                   in_spec=P(None, "model"), out_spec=P(None, "model"),
                   mesh_axes={"model": 1})
    sched = make_schedule("ddpm_linear", 8)
    with pytest.raises(ValueError, match="mesh axis 'model'"):
        make_sharded_sampler(make_mesh((1,), ("time",)), "time", den, sched,
                             SolverConfig("ddim"), SRDSConfig(num_blocks=4))
