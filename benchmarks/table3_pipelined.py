"""Paper Table 3: vanilla vs wavefront-pipelined SRDS.  Supersteps of the
real shard_map wavefront sampler are measured in a fake-device subprocess;
each superstep is ONE lockstep batched model eval (the paper's eff-serial
unit)."""
import json, os, subprocess, sys
import jax
from repro.core import SolverConfig, SRDSConfig, make_schedule
from .common import emit, run_pair, toy_denoiser

CODE = r"""
import jax, json
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
from repro.core import *
from repro.core.pipelined import make_pipelined_sampler

N = {n}; B = {b}
w = jax.random.normal(jax.random.PRNGKey(0), (8, 8), dtype=jnp.float64) * 0.4
model_fn = lambda x, t: jnp.tanh(x @ w) * (0.4 + 3e-4 * t)
from repro.compat import make_mesh
mesh = make_mesh((B,), ("time",))
sched = make_schedule("ddpm_linear", N)
sched = DiffusionSchedule(ab=sched.ab.astype(jnp.float64),
                          t_model=sched.t_model.astype(jnp.float64))
x0 = jax.random.normal(jax.random.PRNGKey(1), (1, 8), dtype=jnp.float64)
samp = make_pipelined_sampler(mesh, "time", model_fn, sched,
                              SolverConfig("ddim"), SRDSConfig(tol=1e-4))
res, steps, evals = samp(x0)
ref = sample_sequential(model_fn, sched, SolverConfig("ddim"), x0)
print(json.dumps({{"supersteps": int(steps), "iters": int(res.iterations),
                  "evals": int(evals),
                  "err": float(jnp.mean(jnp.abs(res.sample - ref)))}}))
"""


def main():
    model_fn = toy_denoiser()
    x0 = jax.random.normal(jax.random.PRNGKey(1), (1, 16))
    for n, b in [(961, 31), (196, 14), (25, 5)]:
        sched = make_schedule("ddpm_linear", n)
        r = run_pair(model_fn, sched, SolverConfig("ddim"), x0,
                     SRDSConfig(tol=1e-3, num_blocks=b))
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS=f"--xla_force_host_platform_device_count={b}",
                   PYTHONPATH="src")
        out = subprocess.run([sys.executable, "-c", CODE.format(n=n, b=b)],
                             capture_output=True, text=True, env=env)
        wf = json.loads(out.stdout.strip().splitlines()[-1]) \
            if out.returncode == 0 else {"supersteps": -1, "iters": -1,
                                         "evals": -1, "err": -1}
        emit(f"table3/ddim{n}", r["t_srds"] * 1e6,
             f"seq_evals={n};vanilla_eff={r['eff_serial']};"
             f"pipelined_supersteps={wf['supersteps']};"
             f"pipelined_iters={wf['iters']};wf_evals={wf['evals']};"
             f"wf_err={wf['err']:.1e}")


if __name__ == "__main__":
    main()
