"""Distributed SRDS (shard_map + wavefront) equivalence — 8 fake devices in
subprocesses so the main test session keeps a single device."""
import pytest

from conftest import run_subprocess

pytestmark = [pytest.mark.slow, pytest.mark.distributed]

COMMON = r"""
import jax
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
from repro.core import *
from repro.core.pipelined import make_sharded_sampler, make_pipelined_sampler

assert len(jax.devices()) == 8
w = jax.random.normal(jax.random.PRNGKey(0), (6, 6), dtype=jnp.float64) * 0.3
def model_fn(x, t):
    return jnp.tanh(x @ w) * (0.5 + 0.001 * t)
from repro.compat import make_mesh
mesh = make_mesh((8,), ("time",))
N = 64
sched = make_schedule("ddpm_linear", N)
sched = DiffusionSchedule(ab=sched.ab.astype(jnp.float64),
                          t_model=sched.t_model.astype(jnp.float64))
x0 = jax.random.normal(jax.random.PRNGKey(1), (2, 6), dtype=jnp.float64)
solver = SolverConfig("ddim")
ref = sample_sequential(model_fn, sched, solver, x0)
"""


def _run(body):
    r = run_subprocess(COMMON + body, devices=8)
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr}"
    return r.stdout


def test_sharded_exact():
    _run(r"""
samp = make_sharded_sampler(mesh, "time", model_fn, sched, solver,
                            SRDSConfig(tol=0.0, num_blocks=8))
res = samp(x0)
assert float(jnp.max(jnp.abs(res.sample - ref))) < 1e-10
assert int(res.iterations) <= 8
""")


def test_sharded_multiple_blocks_per_device():
    _run(r"""
samp = make_sharded_sampler(mesh, "time", model_fn, sched, solver,
                            SRDSConfig(tol=0.0, num_blocks=16))
res = samp(x0)
assert float(jnp.max(jnp.abs(res.sample - ref))) < 1e-10
""")


def test_sharded_matches_single_program():
    """Distributed == single-program SRDS, iteration for iteration."""
    _run(r"""
for tol in (0.0, 1e-4):
    cfg = SRDSConfig(tol=tol, num_blocks=8)
    res_d = make_sharded_sampler(mesh, "time", model_fn, sched, solver, cfg)(x0)
    res_s = srds_sample(model_fn, sched, solver, x0, cfg)
    assert int(res_d.iterations) == int(res_s.iterations), (tol,)
    assert float(jnp.max(jnp.abs(res_d.sample - res_s.sample))) < 1e-10
""")


def test_wavefront_exact_and_superstep_model():
    """Wavefront == sequential; supersteps == k*S + B - 1 (paper Fig. 4)."""
    _run(r"""
samp = make_pipelined_sampler(mesh, "time", model_fn, sched, solver,
                              SRDSConfig(tol=0.0))
res, steps, evals = samp(x0)
assert float(jnp.max(jnp.abs(res.sample - ref))) < 1e-10
k = int(res.iterations); S = N // 8
assert int(steps) <= k * S + 8 + 2, (int(steps), k)
# retirement: device i stops evaluating after refinement i+1, so physical
# evals stay strictly below all-devices-every-superstep
assert 0 < int(evals) < int(steps) * 8 * 2, (int(evals), int(steps))
""")


def test_wavefront_early_convergence():
    _run(r"""
samp = make_pipelined_sampler(mesh, "time", model_fn, sched, solver,
                              SRDSConfig(tol=1e-4))
res, steps, evals = samp(x0)
k = int(res.iterations)
assert k < 8, k
assert float(jnp.mean(jnp.abs(res.sample - ref))) < 1e-3
# latency model: supersteps ~ k*S + B - 1 << sequential N (=64 evals) for
# converged k; each superstep is ONE lockstep batched model eval.
assert int(steps) < N, (int(steps), N)
""")


def test_delta_history_contract_parity_across_samplers():
    """All three samplers return the same delta_history contract:
    (max_iters,) f32, real residuals up to `iterations`, +inf beyond —
    the wavefront used to return a dummy (1,) +inf placeholder."""
    _run(r"""
import numpy as np
cfg = SRDSConfig(tol=1e-4, num_blocks=8)
res_seq = srds_sample(model_fn, sched, solver, x0, cfg)
res_sh = make_sharded_sampler(mesh, "time", model_fn, sched, solver, cfg)(x0)
res_wf, steps, _ = make_pipelined_sampler(mesh, "time", model_fn, sched,
                                          solver, SRDSConfig(tol=1e-4))(x0)
assert res_wf.delta_history.shape == res_sh.delta_history.shape \
    == res_seq.delta_history.shape == (8,), res_wf.delta_history.shape
for res in (res_seq, res_sh, res_wf):
    k = int(res.iterations)
    h = np.asarray(res.delta_history)
    assert np.all(np.isfinite(h[:k])), h
    assert np.all(np.isinf(h[k:])), h
    assert float(res.final_delta) == float(h[k - 1])
# the wavefront residuals are the same quantity the engine computes
# (||x_B^p - x_B^{p-1}||), just wavefront-scheduled
k = min(int(res_wf.iterations), int(res_seq.iterations))
np.testing.assert_allclose(np.asarray(res_wf.delta_history[:k]),
                           np.asarray(res_seq.delta_history[:k]),
                           rtol=1e-4, atol=1e-9)
""")


def test_sharded_batched_per_sample_gating():
    """Distributed batched sampler: per-sample gating with a mixed-tol
    vector is bit-identical to the single-program batched run, lane for
    lane, and each lane stops at its own tolerance."""
    _run(r"""
import numpy as np
xb = jax.random.normal(jax.random.PRNGKey(3), (4, 6), dtype=jnp.float64) \
    * jnp.linspace(0.4, 2.0, 4)[:, None]
tols = jnp.array([1e-2, 1e-4, 1e-6, 1e-3], jnp.float32)
cfg = SRDSConfig(per_sample=True, num_blocks=8)
res_s = srds_sample(model_fn, sched, solver, xb, cfg, tol=tols)
res_d = make_sharded_sampler(mesh, "time", model_fn, sched, solver, cfg)(xb, tols)
assert res_d.iterations.shape == (4,) and res_d.delta_history.shape == (8, 4)
assert np.array_equal(np.asarray(res_d.iterations), np.asarray(res_s.iterations))
assert len(set(np.asarray(res_d.iterations).tolist())) > 1
assert bool(jnp.all(res_d.sample == res_s.sample))
assert np.array_equal(np.asarray(res_d.delta_history),
                      np.asarray(res_s.delta_history))
# per-lane: converged lanes are below their own tolerance
for k in range(4):
    if int(res_d.iterations[k]) < 8:
        assert float(res_d.final_delta[k]) < float(tols[k])
""")


def test_wavefront_per_sample_done_flag():
    """Per-sample wavefront: the psum'd done-flag fires only when EVERY
    sample converged; per-sample iterations/history ride the carry and
    early-converged lanes freeze at their convergence value."""
    _run(r"""
import numpy as np
xb = jax.random.normal(jax.random.PRNGKey(3), (2, 6), dtype=jnp.float64) \
    * jnp.array([[0.4], [2.0]])
refb = sample_sequential(model_fn, sched, solver, xb)
samp = make_pipelined_sampler(mesh, "time", model_fn, sched, solver,
                              SRDSConfig(tol=1e-4, per_sample=True))
res, steps, _ = samp(xb)
assert res.iterations.shape == (2,) and res.delta_history.shape == (8, 2)
it = np.asarray(res.iterations)
assert it.min() >= 1 and it.max() <= 8
# the loop ran to the SLOWEST lane: supersteps cover max(it) refinements
S = N // 8
assert int(steps) >= (int(it.max()) - 1) * S + 8
for k in range(2):
    h = np.asarray(res.delta_history[:, k])
    assert np.all(np.isfinite(h[:it[k]])) and np.all(np.isinf(h[it[k]:]))
    if it[k] < 8:
        assert float(res.final_delta[k]) < 1e-4
assert float(jnp.mean(jnp.abs(res.sample - refb))) < 1e-3
# lanes match the single-program per-sample run, iteration for iteration
res_s = srds_sample(model_fn, sched, solver, xb,
                    SRDSConfig(per_sample=True, num_blocks=8, tol=1e-4))
assert np.array_equal(it, np.asarray(res_s.iterations))
""")


def test_wavefront_short_blocks_respect_iteration_budget():
    """Regression: with s_steps <= 3 the superstep budget's ramp slack let
    the tail complete an uncounted extra refinement — iterations could
    report max_iters+1 with a final_delta never recorded in the history."""
    _run(r"""
import numpy as np
sched16 = make_schedule("ddpm_linear", 16)
sched16 = DiffusionSchedule(ab=sched16.ab.astype(jnp.float64),
                            t_model=sched16.t_model.astype(jnp.float64))
ref16 = sample_sequential(model_fn, sched16, solver, x0)
samp = make_pipelined_sampler(mesh, "time", model_fn, sched16, solver,
                              SRDSConfig(tol=0.0))   # s_steps = 2
res, steps, _ = samp(x0)
k = int(res.iterations)
assert k <= 8, k
h = np.asarray(res.delta_history)
assert h.shape == (8,)
assert float(res.final_delta) == float(h[k - 1]), (res.final_delta, h)
assert float(jnp.max(jnp.abs(res.sample - ref16))) < 1e-10
""")


def test_serving_engine_sharded_fine_solves():
    """DiffusionSamplingEngine's mesh path (shard_map fine solves +
    all_gather) returns the same results as the single-program path."""
    _run(r"""
import numpy as np
from repro.serve.diffusion import DiffusionSamplingEngine, SampleRequest

scale = jnp.linspace(0.5, 1.5, 6)
emodel = lambda x, t: jnp.tanh(x * scale) * (0.5 + 0.001 * t)
reqs = [SampleRequest(seed=i, tol=[1e-2, 1e-4, 1e-5][i % 3]) for i in range(5)]

def run(**kw):
    eng = DiffusionSamplingEngine(emodel, (6,), SolverConfig("ddim"),
                                  num_steps=64, batch_size=2,
                                  dtype=jnp.float64, **kw)
    rids = [eng.submit(r) for r in reqs]
    out = eng.drain()
    return [out[r] for r in rids]

plain = run()
sharded = run(mesh=mesh, axis="time")
for a, b in zip(plain, sharded):
    assert a.iterations == b.iterations
    assert np.array_equal(a.sample, b.sample)
    assert np.array_equal(a.delta_history, b.delta_history)
# B=8 not divisible by a 3-wide axis must fail loudly at program build
from repro.compat import make_mesh
mesh3 = make_mesh((3,), ("t3",), devices=jax.devices()[:3])
eng = DiffusionSamplingEngine(emodel, (6,), SolverConfig("ddim"),
                              num_steps=64, batch_size=2, mesh=mesh3,
                              axis="t3")
eng.submit(SampleRequest(seed=0))
try:
    eng.drain()
    raise SystemExit("expected ValueError for indivisible block split")
except ValueError as e:
    assert "not divisible" in str(e), e
""")


def test_serving_engine_data_sharded_slot_batch():
    """The slot batch itself shards over a ``data`` mesh axis (the 2-device
    CPU mesh): lanes are independent, so results stay bit-identical to the
    unsharded engine — and an indivisible batch_size fails at construction."""
    code = r"""
import jax
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
import numpy as np
from repro.compat import make_mesh
from repro.core import SolverConfig
from repro.serve.diffusion import DiffusionSamplingEngine, SampleRequest

assert len(jax.devices()) == 2
mesh = make_mesh((2,), ("data",))
scale = jnp.linspace(0.5, 1.5, 6)
emodel = lambda x, t: jnp.tanh(x * scale) * (0.5 + 0.001 * t)
reqs = [SampleRequest(seed=i, tol=[1e-2, 1e-4, 1e-5][i % 3]) for i in range(5)]

def run(**kw):
    eng = DiffusionSamplingEngine(emodel, (6,), SolverConfig("ddim"),
                                  num_steps=64, batch_size=2,
                                  dtype=jnp.float64, **kw)
    rids = [eng.submit(r) for r in reqs]
    out = eng.drain()
    return [out[r] for r in rids]

plain = run()
sharded = run(mesh=mesh, data_axis="data")
for a, b in zip(plain, sharded):
    assert a.iterations == b.iterations
    assert np.array_equal(a.sample, b.sample)
    assert np.array_equal(a.delta_history, b.delta_history)
# batch_size=3 doesn't split over a 2-wide data axis: loud, at construction
try:
    DiffusionSamplingEngine(emodel, (6,), SolverConfig("ddim"), num_steps=64,
                            batch_size=3, mesh=mesh, data_axis="data")
    raise SystemExit("expected ValueError for indivisible batch_size")
except ValueError as e:
    assert "not divisible" in str(e), e
print("DATA SHARD OK")
"""
    r = run_subprocess(code, devices=2)
    assert r.returncode == 0 and "DATA SHARD OK" in r.stdout, \
        f"stdout={r.stdout}\nstderr={r.stderr}"


def test_serving_engine_block_and_data_axes_compose():
    """Block-parallel fine solves and a sharded slot batch compose on one
    2D mesh — still bit-identical to the unsharded engine."""
    code = r"""
import jax
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
import numpy as np
from repro.compat import make_mesh
from repro.core import SolverConfig
from repro.serve.diffusion import DiffusionSamplingEngine, SampleRequest

assert len(jax.devices()) == 8
mesh = make_mesh((4, 2), ("time", "data"))
scale = jnp.linspace(0.5, 1.5, 6)
emodel = lambda x, t: jnp.tanh(x * scale) * (0.5 + 0.001 * t)
reqs = [SampleRequest(seed=i, tol=[1e-2, 1e-4, 1e-5][i % 3]) for i in range(6)]

def run(**kw):
    eng = DiffusionSamplingEngine(emodel, (6,), SolverConfig("ddim"),
                                  num_steps=64, batch_size=2,
                                  dtype=jnp.float64, **kw)
    rids = [eng.submit(r) for r in reqs]
    out = eng.drain()
    return [out[r] for r in rids]

plain = run()
both = run(mesh=mesh, axis="time", data_axis="data")
for a, b in zip(plain, both):
    assert a.iterations == b.iterations
    assert np.array_equal(a.sample, b.sample)
    assert np.array_equal(a.delta_history, b.delta_history)
print("2D SHARD OK")
"""
    r = run_subprocess(code, devices=8)
    assert r.returncode == 0 and "2D SHARD OK" in r.stdout, \
        f"stdout={r.stdout}\nstderr={r.stderr}"


def test_dit_denoiser_composes_time_data_model_mesh():
    """Real DiT fine solves over a (2 time, 2 data, 2 model) mesh through
    the one Denoiser seam: the patch-sharded backbone (K/V all-gather over
    ``model``) matches the single-device driver within the documented
    shape-dependent-gemm carve-out, in all three drivers — ``srds_sample``
    (vmap-of-shard_map), the sharded driver (``inner_eval`` glue inside the
    time/data shard_map), and the serving engine (``inner_eval`` inside
    its replicated program shard_map)."""
    code = r"""
import dataclasses as dc
import jax
import jax.numpy as jnp
from repro.configs.base import get_arch
from repro.configs.srds_dit import dit_denoiser
from repro.core import SRDSConfig, SolverConfig, make_schedule, srds_sample
from repro.core.pipelined import make_sharded_sampler
from repro.launch.mesh import make_srds_mesh
from repro.models.dit import init_dit
from repro.serve.diffusion import DiffusionSamplingEngine, SampleRequest

assert len(jax.devices()) == 8
cfg = dc.replace(get_arch("srds-dit-cifar"), num_layers=2, d_model=32,
                 num_heads=2, num_kv_heads=2, head_dim=16, d_ff=64,
                 patch_size=2, dtype="float32")
params = init_dit(cfg, jax.random.PRNGKey(0))
mesh = make_srds_mesh(2, 2, 2)
assert dict(mesh.shape) == {"time": 2, "data": 2, "model": 2}
# H=8 rows over model=2 -> 4 local rows, /patch_size=2 -> 2 patch rows each
den = dit_denoiser(cfg, params, shard_axis="model", mesh=mesh,
                   use_kernel=False)
ref_fn = dit_denoiser(cfg, params, use_kernel=False)
sched = make_schedule("ddpm_linear", 8)
solver = SolverConfig("ddim")
x0 = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 8, 3))
cfg_s = SRDSConfig(num_blocks=4, per_sample=True)
TOL = 5e-5   # documented shape-dependent-gemm carve-out (f32)

r_ref = srds_sample(ref_fn, sched, solver, x0, cfg_s)
r_mp = srds_sample(den, sched, solver, x0, cfg_s)
d1 = float(jnp.max(jnp.abs(r_ref.sample - r_mp.sample)))
assert d1 <= TOL, d1

samp = make_sharded_sampler(mesh, "time", den, sched, solver, cfg_s,
                            data_axis="data")
d2 = float(jnp.max(jnp.abs(r_ref.sample - samp(x0).sample)))
assert d2 <= TOL, d2

eng = DiffusionSamplingEngine(den, (8, 8, 3), solver=solver, num_steps=8,
                              batch_size=4, num_blocks=4, mesh=mesh,
                              data_axis="data")
eng_ref = DiffusionSamplingEngine(ref_fn, (8, 8, 3), solver=solver,
                                  num_steps=8, batch_size=4, num_blocks=4)
for e in (eng, eng_ref):
    for i in range(4):
        e.submit(SampleRequest(seed=i, tol=1e-3))
out, out_ref = eng.drain(), eng_ref.drain()
d3 = max(float(jnp.max(jnp.abs(out[k].sample - out_ref[k].sample)))
         for k in out)
assert d3 <= TOL, d3

# one flash-kernel eval (Pallas interpret mode on CPU) through the seam
den_k = dit_denoiser(cfg, params, shard_axis="model", mesh=mesh)
d4 = float(jnp.max(jnp.abs(den_k(x0, 0.5)
                           - dit_denoiser(cfg, params)(x0, 0.5))))
assert d4 <= TOL, d4
print("DIT TDM MESH OK", d1, d2, d3, d4)
"""
    r = run_subprocess(code, devices=8, timeout=900)
    assert r.returncode == 0 and "DIT TDM MESH OK" in r.stdout, \
        f"stdout={r.stdout}\nstderr={r.stderr}"


def test_straggler_mitigation_preserves_exactness():
    """Transient stragglers (stale fine results) cost iterations, never
    correctness."""
    _run(r"""
def strag(p):
    m = jnp.zeros((8,), bool).at[3].set(True).at[5].set(True)
    return jnp.where(p % 2 == 1, m, jnp.zeros((8,), bool))
samp = make_sharded_sampler(mesh, "time", model_fn, sched, solver,
                            SRDSConfig(tol=0.0, num_blocks=8, max_iters=24),
                            straggler_fn=strag)
res = samp(x0)
assert float(jnp.max(jnp.abs(res.sample - ref))) < 1e-10
base = make_sharded_sampler(mesh, "time", model_fn, sched, solver,
                            SRDSConfig(tol=1e-6, num_blocks=8, max_iters=24))(x0)
withs = make_sharded_sampler(mesh, "time", model_fn, sched, solver,
                             SRDSConfig(tol=1e-6, num_blocks=8, max_iters=24),
                             straggler_fn=strag)(x0)
assert int(withs.iterations) >= int(base.iterations)
""")


def test_sharded_truncation_matches_untruncated():
    """Converged-prefix truncation under shard_map: the suffix is
    redistributed over the axis (retired prefix blocks free whole
    devices).  Iterations and (f32) delta_history match the untruncated
    distributed run bitwise; samples match to a few f64 ulps — under
    shard_map the while_loop -> unrolled-cond swap perturbs XLA's loop
    codegen in the last bits even for identical math (the same effect
    makes while vs scan differ here), so the single-program driver's
    bitwise guarantee relaxes to ulp-level for the sharded one."""
    _run(r"""
import numpy as np
scale = jnp.linspace(0.5, 1.5, 6)
emodel = lambda x, t: jnp.tanh(x * scale) * (0.5 + 0.001 * t)
eref = sample_sequential(emodel, sched, solver, x0)
for tol in (0.0, 1e-4):
    cfg_p = SRDSConfig(tol=tol, num_blocks=8)
    cfg_t = SRDSConfig(tol=tol, num_blocks=8, truncate=True)
    res_p = make_sharded_sampler(mesh, "time", emodel, sched, solver, cfg_p)(x0)
    res_t = make_sharded_sampler(mesh, "time", emodel, sched, solver, cfg_t)(x0)
    assert int(res_p.iterations) == int(res_t.iterations), tol
    assert np.array_equal(np.asarray(res_p.delta_history),
                          np.asarray(res_t.delta_history)), tol
    np.testing.assert_allclose(np.asarray(res_t.sample),
                               np.asarray(res_p.sample),
                               rtol=0, atol=1e-12, err_msg=str(tol))
    res_s = srds_sample(emodel, sched, solver, x0, cfg_t)
    np.testing.assert_allclose(np.asarray(res_t.sample),
                               np.asarray(res_s.sample),
                               rtol=0, atol=1e-12, err_msg=str(tol))
    if tol == 0.0 and \
            float(jnp.max(jnp.abs(res_t.sample - eref))) > 1e-10:
        raise SystemExit("truncated sharded run lost exactness")
# 16 blocks on 8 devices: truncation shrinks per-device chunks too
cfg16 = SRDSConfig(tol=0.0, num_blocks=16, truncate=True)
res16 = make_sharded_sampler(mesh, "time", emodel, sched, solver, cfg16)(x0)
ref16 = make_sharded_sampler(mesh, "time", emodel, sched, solver,
                             SRDSConfig(tol=0.0, num_blocks=16))(x0)
assert int(res16.iterations) == int(ref16.iterations)
np.testing.assert_allclose(np.asarray(res16.sample),
                           np.asarray(ref16.sample), rtol=0, atol=1e-12)
""")


def test_sharded_truncation_rejects_stragglers():
    _run(r"""
try:
    make_sharded_sampler(mesh, "time", model_fn, sched, solver,
                         SRDSConfig(tol=0.0, num_blocks=8, truncate=True),
                         straggler_fn=lambda p: jnp.zeros((8,), bool))(x0)
    raise SystemExit("expected ValueError for truncate + straggler_fn")
except ValueError as e:
    assert "straggler" in str(e), e
""")


def test_sharded_sampler_data_axis_runtime_tol():
    """make_sharded_sampler's runtime-tol path shards the K sample batch
    over a data mesh axis (2D (time, data) mesh): bit-identical to the
    unsharded per-sample run, lane for lane — and non-per-sample configs
    are rejected."""
    code = r"""
import jax
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
import numpy as np
from repro.core import *
from repro.core.pipelined import make_sharded_sampler
from repro.compat import make_mesh

assert len(jax.devices()) == 8
mesh = make_mesh((4, 2), ("time", "data"))
scale = jnp.linspace(0.5, 1.5, 6)
emodel = lambda x, t: jnp.tanh(x * scale) * (0.5 + 0.001 * t)
N = 64
sched = make_schedule("ddpm_linear", N)
sched = DiffusionSchedule(ab=sched.ab.astype(jnp.float64),
                          t_model=sched.t_model.astype(jnp.float64))
solver = SolverConfig("ddim")
xb = jax.random.normal(jax.random.PRNGKey(3), (4, 6), dtype=jnp.float64) \
    * jnp.linspace(0.4, 2.0, 4)[:, None]
tols = jnp.array([1e-2, 1e-4, 1e-6, 1e-3], jnp.float32)
cfg = SRDSConfig(per_sample=True, num_blocks=8)
res_s = srds_sample(emodel, sched, solver, xb, cfg, tol=tols)
samp = make_sharded_sampler(mesh, "time", emodel, sched, solver, cfg,
                            data_axis="data")
res_d = samp(xb, tols)
assert np.array_equal(np.asarray(res_d.iterations), np.asarray(res_s.iterations))
assert bool(jnp.all(res_d.sample == res_s.sample))
assert np.array_equal(np.asarray(res_d.delta_history),
                      np.asarray(res_s.delta_history))
# scalar runtime tol broadcasts over the sharded batch
res_sc = samp(xb, 1e-4)
res_sc_ref = srds_sample(emodel, sched, solver, xb, cfg,
                         tol=jnp.full((4,), 1e-4, jnp.float32))
assert bool(jnp.all(res_sc.sample == res_sc_ref.sample))
# joint-norm gating cannot shard the batch: loud error
try:
    make_sharded_sampler(mesh, "time", emodel, sched, solver,
                         SRDSConfig(num_blocks=8), data_axis="data")
    raise SystemExit("expected ValueError without per_sample")
except ValueError as e:
    assert "per_sample" in str(e) or "per-sample" in str(e), e
# K=3 does not divide the 2-wide data axis: loud error at call time
try:
    samp(xb[:3], tols[:3])
    raise SystemExit("expected ValueError for indivisible K")
except ValueError as e:
    assert "not divisible" in str(e), e
# truncation composes with the data-sharded batch (samples to a few f64
# ulps: under shard_map the unrolled-cond loop codegen shifts last bits)
cfg_t = SRDSConfig(per_sample=True, num_blocks=8, truncate=True)
res_t = make_sharded_sampler(mesh, "time", emodel, sched, solver, cfg_t,
                             data_axis="data")(xb, tols)
assert np.array_equal(np.asarray(res_t.iterations),
                      np.asarray(res_s.iterations))
np.testing.assert_allclose(np.asarray(res_t.sample),
                           np.asarray(res_s.sample), rtol=0, atol=1e-12)
print("DATA AXIS OK")
"""
    r = run_subprocess(code, devices=8)
    assert r.returncode == 0 and "DATA AXIS OK" in r.stdout, \
        f"stdout={r.stdout}\nstderr={r.stderr}"


def test_sharded_window_policies():
    """The FrontierPolicy seam on the sharded driver: window=ExactPrefix()
    is the same compiled program as truncate=True (bitwise — the
    documented ulp caveat is vs the UNtruncated engine, not between these
    two), and the residual window stays serial-close at its tol."""
    _run(r"""
import numpy as np
from repro.core import ExactPrefix, ResidualWindow
scale = jnp.linspace(0.5, 1.5, 6)
emodel = lambda x, t: jnp.tanh(x * scale) * (0.5 + 0.001 * t)
eref = sample_sequential(emodel, sched, solver, x0)
cfg_t = SRDSConfig(tol=1e-4, num_blocks=8, truncate=True)
cfg_w = SRDSConfig(tol=1e-4, num_blocks=8, window=ExactPrefix())
rt = make_sharded_sampler(mesh, "time", emodel, sched, solver, cfg_t)(x0)
rw = make_sharded_sampler(mesh, "time", emodel, sched, solver, cfg_w)(x0)
assert int(rt.iterations) == int(rw.iterations)
assert bool(jnp.all(rt.sample == rw.sample))
assert np.array_equal(np.asarray(rt.delta_history),
                      np.asarray(rw.delta_history))
cfg_r = SRDSConfig(tol=1e-4, num_blocks=8, window=ResidualWindow(1e-3))
rr = make_sharded_sampler(mesh, "time", emodel, sched, solver, cfg_r)(x0)
assert float(jnp.max(jnp.abs(rr.sample - eref))) < 5e-2
""")


def test_wavefront_retirement_consults_policy():
    """Per-device retirement now rides FrontierPolicy.retire_at: the
    default (ExactPrefix rule) skips retired devices' evals; an explicit
    FixedBudget window disables retirement — same results, strictly more
    physical evals."""
    _run(r"""
from repro.core import FixedBudget
samp = make_pipelined_sampler(mesh, "time", model_fn, sched, solver,
                              SRDSConfig(tol=0.0))
samp_nb = make_pipelined_sampler(mesh, "time", model_fn, sched, solver,
                                 SRDSConfig(tol=0.0, window=FixedBudget()))
res, steps, evals = samp(x0)
res2, steps2, evals2 = samp_nb(x0)
assert float(jnp.max(jnp.abs(res.sample - res2.sample))) < 1e-12
assert int(res.iterations) == int(res2.iterations)
assert int(steps) == int(steps2)
# retirement is the only difference: disabling it must cost strictly more
assert int(evals2) > int(evals), (int(evals2), int(evals))
""")
