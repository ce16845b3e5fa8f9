"""Serving engine + sharding-rule unit tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops as kops

kops.FORCE_REF = True

from repro.configs import get_arch
from repro.models import forward_train, init_params
from repro.serve import Request, ServingEngine


KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("arch", ["qwen3-8b", "rwkv6-1.6b"])
def test_engine_greedy_matches_full_forward(arch):
    """Engine greedy decode == argmax over the full-sequence forward run
    on the concatenated prompt+generation (teacher-forced check)."""
    cfg = get_arch(arch).reduced()
    params = init_params(cfg, KEY)
    engine = ServingEngine(cfg, params, batch_size=2, max_seq=64)
    prompt = jax.random.randint(KEY, (12,), 0, cfg.vocab_size)
    outs = engine.generate([Request(prompt=prompt, max_new_tokens=6)])
    gen = outs[0]
    # teacher-forced verification of the first generated token
    logits, _, _ = forward_train(cfg, params, {"tokens": prompt[None]})
    first = int(jnp.argmax(logits[0, -1, :cfg.vocab_size]))
    assert gen[0] == first
    # and of the second (condition on the first generated token)
    seq2 = jnp.concatenate([prompt, jnp.array([gen[0]], jnp.int32)])
    logits2, _, _ = forward_train(cfg, params, {"tokens": seq2[None]})
    second = int(jnp.argmax(logits2[0, -1, :cfg.vocab_size]))
    assert gen[1] == second


def test_engine_ragged_batch():
    cfg = get_arch("stablelm-3b").reduced()
    params = init_params(cfg, KEY)
    engine = ServingEngine(cfg, params, batch_size=3, max_seq=64)
    reqs = [Request(prompt=jax.random.randint(jax.random.fold_in(KEY, i),
                                              (4 + 3 * i,), 0, cfg.vocab_size),
                    max_new_tokens=4) for i in range(3)]
    outs = engine.generate(reqs)
    assert all(len(o) == 4 for o in outs)
    assert all(0 <= t < cfg.vocab_size for o in outs for t in o)


# --------------------------------------------------------------------------
# sharding rules (pure unit tests on PartitionSpecs — no devices needed)
# --------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.distributed
def test_param_sharding_rules_subprocess():
    from conftest import run_subprocess
    code = r"""
import jax
from jax.sharding import PartitionSpec as P
from repro.compat import make_mesh
from repro.configs import get_arch
from repro.launch.specs import param_specs
from repro.models.transformer import ParallelCtx
from repro.parallel.sharding import param_shardings

mesh = make_mesh((2, 4), ("data", "model"))
par = ParallelCtx(mesh=mesh, model_parallel=4)

# dense arch: TP rules
cfg = get_arch("stablelm-3b")
ps = param_specs(cfg, par)
sh = param_shardings(cfg, mesh, ps, par)
assert sh["embed"]["table"].spec == P("model", None)
assert sh["unembed"]["w"].spec == P(None, "model")
assert sh["blocks"]["attn"]["wq"].spec == P(None, None, "model")
assert sh["blocks"]["attn"]["wo"].spec == P(None, "model", None)
assert sh["blocks"]["mlp"]["w_up"].spec == P(None, None, "model")
assert sh["blocks"]["mlp"]["w_down"].spec == P(None, "model", None)
# FSDP adds the data dim
shf = param_shardings(cfg, mesh, ps, par, fsdp=True)
assert shf["blocks"]["mlp"]["w_up"].spec == P(None, "data", "model")

# MoE arch: EP rules
cfg = get_arch("arctic-480b")
ps = param_specs(cfg, par)
sh = param_shardings(cfg, mesh, ps, par)
assert sh["blocks"]["moe"]["w_up"].spec == P(None, "data", None, "model")
assert sh["blocks"]["moe"]["w_down"].spec == P(None, "data", "model", None)
assert sh["blocks"]["moe"]["router"].spec == P(None, None, None)
# kv heads (8) not divisible by wider TP stay replicated
mesh16 = make_mesh((2, 16), ("data", "model"))
par16 = ParallelCtx(mesh=mesh16, model_parallel=16)
cfgq = get_arch("qwen3-8b")
sh = param_shardings(cfgq, mesh16, param_specs(cfgq, par16), par16)
assert sh["blocks"]["attn"]["wk"].spec == P(None, None, None)
assert sh["blocks"]["attn"]["wq"].spec == P(None, None, "model")
print("SHARDING RULES OK")
"""
    r = run_subprocess(code, devices=32, timeout=600)
    assert r.returncode == 0 and "SHARDING RULES OK" in r.stdout, \
        f"{r.stdout}\n{r.stderr[-3000:]}"


@pytest.mark.slow
@pytest.mark.distributed
def test_cache_sharding_rules_subprocess():
    from conftest import run_subprocess
    code = r"""
import jax
from jax.sharding import PartitionSpec as P
from repro.compat import make_mesh
from repro.configs import get_arch, SHAPES
import dataclasses as dc
from repro.launch.specs import cache_specs
from repro.models.transformer import ParallelCtx
from repro.parallel.sharding import cache_shardings

mesh = make_mesh((2, 4), ("data", "model"))
par = ParallelCtx(mesh=mesh, model_parallel=4)
cfg = get_arch("qwen3-8b")
shape = dc.replace(SHAPES["decode_32k"], seq_len=128, global_batch=8)
cs = cache_specs(cfg, shape, par)
sh = cache_shardings(cfg, mesh, cs, par)
k_sh, v_sh = sh
# flash-decoding layout: KV sequence over model, batch over data
assert k_sh.spec == P(None, ("data",), "model", None, None), k_sh.spec
print("CACHE RULES OK")
"""
    r = run_subprocess(code, devices=32, timeout=600)
    assert r.returncode == 0 and "CACHE RULES OK" in r.stdout, \
        f"{r.stdout}\n{r.stderr[-3000:]}"


# --------------------------------------------------------------------------
# the diffusion engine's programs take the denoiser's weights as argument 0
# --------------------------------------------------------------------------

import dataclasses                                    # noqa: E402
import re                                             # noqa: E402

from repro.configs.srds_dit import dit_denoiser       # noqa: E402
from repro.core import SolverConfig                   # noqa: E402
from repro.core.accel import TriangularAccel          # noqa: E402
from repro.core.denoiser import Denoiser, as_denoiser  # noqa: E402
from repro.core.window import ResidualWindow          # noqa: E402
from repro.models.dit import init_dit                 # noqa: E402
from repro.serve.diffusion import (DiffusionSamplingEngine,  # noqa: E402
                                   SampleRequest)

SHAPE = (32, 32, 3)
CONSTANT = re.compile(r"= (\w+)\[([\d,]*)\]\S* constant\(")
ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s32": 4,
            "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8}


def _tiny_dit():
    return dataclasses.replace(get_arch("srds-dit-cifar"), num_layers=2,
                               d_model=64, num_heads=4, num_kv_heads=4,
                               d_ff=256)


def _program_args(eng, program):
    """A program of ``eng``'s one group and its arguments, weights first."""
    init_fn, step_for, B, _ = eng._build_program(
        eng.num_steps, eng.schedule, eng.sample_shape, eng.solver)
    K = eng.batch_size
    args = [eng.denoiser.params, jnp.zeros((K,) + eng.sample_shape),
            jnp.zeros((B, K) + eng.sample_shape),
            jnp.zeros((B, K) + eng.sample_shape), jnp.zeros((K,), bool)]
    if program == "init":
        return init_fn, args
    kind, minf = program.split(":")
    if kind == "window":
        return step_for.windowed(int(minf)), args + [jnp.int32(0)]
    return step_for(int(minf)), args


def _largest_constant_bytes(hlo: str) -> int:
    sizes = [int(np.prod([int(d) for d in dims.split(",") if d]))
             * ITEMSIZE[dtype] for dtype, dims in CONSTANT.findall(hlo)]
    return max(sizes, default=0)


@pytest.mark.parametrize("program", ["init", "step:0", "step:2",
                                     "window:0"])
def test_served_programs_take_the_weights_as_an_argument(program):
    """A DiT's init and step programs lowered for two weight seeds are the
    same HLO, and none holds a weight-sized constant: the weights are not
    baked in, and a compile cache keyed on the HLO hits across seeds."""
    cfg = _tiny_dit()
    texts = []
    for seed in (0, 1):
        params = init_dit(cfg, jax.random.PRNGKey(seed))
        window = ResidualWindow() if program.startswith("window") else None
        eng = DiffusionSamplingEngine(
            dit_denoiser(cfg, params, use_kernel=False), SHAPE,
            SolverConfig("ddim"), num_steps=25, batch_size=2, num_blocks=5,
            window=window)
        fn, args = _program_args(eng, program)
        texts.append(fn.lower(*args).as_text(dialect="hlo"))
    assert texts[0] == texts[1]
    # the largest weight of the tiny DiT is its 4096 x 64 position table
    assert _largest_constant_bytes(texts[0]) <= 64 * 1024


def test_served_samples_match_the_closure_form_of_the_same_weights():
    """Weights as program arguments serve the samples that the same
    weights baked in as constants serve, bit for bit on the CPU."""
    cfg = _tiny_dit()
    den = dit_denoiser(cfg, init_dit(cfg, jax.random.PRNGKey(3)),
                       use_kernel=False)
    reqs = [SampleRequest(seed=s, tol=t)
            for s, t in ((0, 1e-2), (1, 1e-3), (2, 1e-2))]
    out = []
    for model in (den, as_denoiser(den.fn)):
        eng = DiffusionSamplingEngine(model, SHAPE, SolverConfig("ddim"),
                                      num_steps=25, batch_size=2,
                                      num_blocks=5)
        rids = [eng.submit(r) for r in reqs]
        got = eng.drain()
        out.append([(got[i].sample, got[i].iterations) for i in rids])
    assert eng.denoiser.params == ()
    for (a, ia), (b, ib) in zip(*out):
        assert ia == ib
        np.testing.assert_array_equal(a, b)


def _toy_weights():
    return {"w": jnp.linspace(0.5, 1.5, 6, dtype=jnp.float32),
            "b": jnp.float32(0.001)}


def _toy_apply(params, x, t):
    return jnp.tanh(x * params["w"]) * (0.5 + params["b"] * t)


@pytest.mark.parametrize("program,accel,donated", [
    ("init", None, {2, 3}),
    ("step:0", None, {2, 3}),
    ("step:0", TriangularAccel, {2, 3, 5}),
    ("window:0", None, {2, 3}),
    ("window:0", TriangularAccel, {2, 3, 6}),
])
def test_engine_donates_the_trajectory_never_the_weights(
        monkeypatch, program, accel, donated):
    """With donation on (as off the CPU), every program donates the
    trajectory buffers (and the mixing state), never argument 0, the
    weights: a donated weight buffer would be freed after one call."""
    with monkeypatch.context() as m:
        m.setattr(jax, "default_backend", lambda: "tpu")
        eng = DiffusionSamplingEngine(
            Denoiser(apply=_toy_apply, params=_toy_weights()), (6,),
            SolverConfig("ddim"), num_steps=16, batch_size=2,
            num_blocks=4, use_fused=False,
            window=ResidualWindow() if program.startswith("window")
            else None, accel=accel() if accel else None)
    assert eng.param_bytes == 6 * 4 + 4
    fn, args = _program_args(eng, program)
    if accel is not None:
        args.append(eng._batch_for(SampleRequest(seed=0,
                                                 num_steps=16)).astate)
    (info, _) = fn.lower(*args).args_info
    got = {i for i, a in enumerate(info)
           if any(leaf.donated for leaf in jax.tree.leaves(a))}
    assert got == donated
    assert not any(leaf.donated for leaf in jax.tree.leaves(info[0]))


@pytest.mark.distributed
def test_engine_places_the_weights_once_on_its_mesh():
    """Without a mesh the programs take the caller's weight arrays as
    they are.  Under a mesh the engine replicates them onto every mesh
    device once, at build time, so a step call moves no weight between
    devices."""
    eng = DiffusionSamplingEngine(
        Denoiser(apply=_toy_apply, params=_toy_weights()), (6,),
        SolverConfig("ddim"), num_steps=16, batch_size=2, num_blocks=4)
    assert eng.params is eng.denoiser.params
    from conftest import run_subprocess
    code = r"""
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import SolverConfig
from repro.core.denoiser import Denoiser
from repro.launch.mesh import make_srds_mesh
from repro.serve.diffusion import DiffusionSamplingEngine

def apply(p, x, t):
    return jnp.tanh(x * p["w"]) * (0.5 + p["b"] * t)

weights = {"w": jnp.linspace(0.5, 1.5, 6), "b": jnp.float32(0.001)}
mesh = make_srds_mesh(time=4, devices=jax.devices()[:4])
eng = DiffusionSamplingEngine(Denoiser(apply=apply, params=weights), (6,),
                              SolverConfig("ddim"), num_steps=16,
                              batch_size=2, num_blocks=4, mesh=mesh,
                              axis="time")
for leaf in jax.tree.leaves(eng.params):
    assert leaf.sharding.is_fully_replicated
    assert leaf.sharding.device_set == set(mesh.devices.flat)
_, step_for, B, _ = eng._build_program(16, eng.schedule, (6,), eng.solver)
rep = NamedSharding(mesh, P())
state = [jax.device_put(a, rep) for a in (
    jnp.ones((2, 6)), jnp.ones((B, 2, 6)), jnp.ones((B, 2, 6)),
    jnp.ones((2,), bool))]
step = step_for(0)
step(eng.params, *state)                                # compile
with jax.transfer_guard("disallow"):
    jax.block_until_ready(step(eng.params, *state))
try:
    with jax.transfer_guard("disallow"):
        step(weights, *state)
except Exception as e:                                  # the caller's arrays
    assert "transfer" in str(e), e
else:
    raise AssertionError("the caller's weights reached the mesh untouched")
print("PLACED ONCE OK")
"""
    r = run_subprocess(code, devices=4, timeout=600)
    assert r.returncode == 0 and "PLACED ONCE OK" in r.stdout, \
        f"{r.stdout}\n{r.stderr[-3000:]}"
