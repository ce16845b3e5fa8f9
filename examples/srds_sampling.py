"""Sampling comparison driver: sequential vs vanilla SRDS vs distributed
(block-parallel + wavefront-pipelined) SRDS, plus the SRDS-native straggler
mitigation — on fake devices so the whole flow runs on this CPU box.

  PYTHONPATH=src python examples/srds_sampling.py  (re-execs with 8 devices)
"""
import json
import os
import subprocess
import sys

CODE = r"""
import jax
import jax.numpy as jnp
from repro.core import (DiffusionSchedule, SolverConfig, SRDSConfig,
                        make_schedule, sample_sequential, srds_sample)
from repro.core.pipelined import make_pipelined_sampler, make_sharded_sampler

jax.config.update("jax_enable_x64", True)
assert len(jax.devices()) == 8
w = jax.random.normal(jax.random.PRNGKey(0), (24, 24), dtype=jnp.float64) * 0.35
model_fn = lambda x, t: jnp.tanh(x @ w) * (0.4 + 3e-4 * t)
N = 64
sched = make_schedule("ddpm_linear", N)
sched = DiffusionSchedule(ab=sched.ab.astype(jnp.float64),
                          t_model=sched.t_model.astype(jnp.float64))
solver = SolverConfig("ddim")
x0 = jax.random.normal(jax.random.PRNGKey(1), (2, 24), dtype=jnp.float64)
from repro.compat import make_mesh
mesh = make_mesh((8,), ("time",))

ref = sample_sequential(model_fn, sched, solver, x0)
print(f"sequential: {N} serial evals")

res = srds_sample(model_fn, sched, solver, x0, SRDSConfig(tol=1e-5))
print(f"vanilla SRDS:     iters={int(res.iterations)} "
      f"err={float(jnp.mean(jnp.abs(res.sample-ref))):.2e}")

from repro.core import iteration_cost, predicted_evals, truncated_evals
res_t = srds_sample(model_fn, sched, solver, x0,
                    SRDSConfig(tol=1e-5, truncate=True))
cost = iteration_cost(N, None, 1)
k = int(res_t.iterations)
print(f"truncated SRDS:   iters={k} bit-identical="
      f"{bool(jnp.all(res_t.sample == res.sample))} "
      f"evals={truncated_evals(cost, k)} vs {predicted_evals(cost, k)} "
      f"untruncated (converged-prefix truncation)")

samp = make_sharded_sampler(mesh, "time", model_fn, sched, solver,
                            SRDSConfig(tol=1e-5, num_blocks=8))
res = samp(x0)
print(f"block-parallel:   iters={int(res.iterations)} "
      f"err={float(jnp.mean(jnp.abs(res.sample-ref))):.2e}  (8 devices)")

samp, = [make_pipelined_sampler(mesh, "time", model_fn, sched, solver,
                                SRDSConfig(tol=1e-5))]
res, steps, evals = samp(x0)
print(f"wavefront:        iters={int(res.iterations)} supersteps={int(steps)} "
      f"physical_evals={int(evals)} "
      f"err={float(jnp.mean(jnp.abs(res.sample-ref))):.2e}  "
      f"(vs {N} sequential evals; retired devices skip theirs)")

def strag(p):
    m = jnp.zeros((8,), bool).at[3].set(True)
    return jnp.where(p % 2 == 1, m, jnp.zeros((8,), bool))
samp = make_sharded_sampler(mesh, "time", model_fn, sched, solver,
                            SRDSConfig(tol=1e-5, num_blocks=8, max_iters=20),
                            straggler_fn=strag)
res = samp(x0)
print(f"with stragglers:  iters={int(res.iterations)} "
      f"err={float(jnp.mean(jnp.abs(res.sample-ref))):.2e}  "
      f"(block 3 stale every other refinement — still exact)")

# --- batched: per-sample convergence gating (mixed-tolerance batch) ---
xb = jax.random.normal(jax.random.PRNGKey(2), (4, 24), dtype=jnp.float64)
tols = jnp.array([1e-2, 1e-3, 1e-4, 1e-5], jnp.float32)
res = srds_sample(model_fn, sched, solver, xb, SRDSConfig(per_sample=True),
                  tol=tols)
print(f"per-sample SRDS:  iters={res.iterations.tolist()} "
      f"for tol={tols.tolist()} (each sample stops at its own tolerance)")
samp = make_sharded_sampler(mesh, "time", model_fn, sched, solver,
                            SRDSConfig(per_sample=True, num_blocks=8))
res_d = samp(xb, tols)
print(f"sharded batched:  iters={res_d.iterations.tolist()} "
      f"(bit-identical to the single-program batched run: "
      f"{bool(jnp.all(res_d.sample == res.sample))})")

# --- the serving layer: micro-batching + slot recycling over a queue ---
from repro.serve.diffusion import DiffusionSamplingEngine, SampleRequest
eng = DiffusionSamplingEngine(model_fn, (24,), solver, num_steps=N,
                              batch_size=4, dtype=jnp.float64)
reqs = [SampleRequest(seed=i, tol=[1e-2, 1e-3, 1e-4, 1e-5][i % 4])
        for i in range(12)]
rids = [eng.submit(r) for r in reqs]
out = eng.drain()
st = eng.stats()
iters = [out[r].iterations for r in rids]
lock = sum(len(g) * (8 + max(g) * 72) for g in
           (iters[i:i+4] for i in range(0, len(iters), 4)))
print(f"serving engine:   {len(reqs)} mixed-tol requests, batch 4 -> "
      f"{st['effective_evals_per_sample']:.0f} evals/sample "
      f"(lockstep gating would pay {lock / len(reqs):.0f})")
"""


def main():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", CODE], env=env)
    sys.exit(r.returncode)


if __name__ == "__main__":
    main()
