"""Paper Table 6 (Appendix D): device scaling of the distributed SRDS
sampler (1/2/4 fake devices, wall-clock per sample) vs ParaDiGMS.

Beyond the single-axis scaling sweep, the ``mesh_t2d2m2`` row exercises
the full (2 time, 2 data, 2 model) composition on 8 fake devices: real
DiT fine solves through the ``repro.core.denoiser`` seam (patch-sharded
attention over ``model``), checked against the single-device driver and
appended into the gated BENCH_core.json artifact (``--out``) — the
``within_tol`` field is a current-run-alone contract in
``check_bench_core``, so a seam that silently loses single-device parity
fails CI even when wall-clock looks fine.
"""
import argparse
import json
import os
import subprocess
import sys

from .common import emit
from .table12_window import merge_out

CODE = r"""
import jax, json, time
import jax.numpy as jnp
from repro.core import *
from repro.core.pipelined import make_sharded_sampler

D = {d}
w = jax.random.normal(jax.random.PRNGKey(0), (16, 16)) * 0.4
model_fn = lambda x, t: jnp.tanh(x @ w) * (0.4 + 3e-4 * t)
from repro.compat import make_mesh
mesh = make_mesh((D,), ("time",))
sched = make_schedule("ddpm_linear", 100)
x0 = jax.random.normal(jax.random.PRNGKey(1), (1, 16))
samp = make_sharded_sampler(mesh, "time", model_fn, sched,
                            SolverConfig("ddim"),
                            SRDSConfig(tol=1e-4, num_blocks=20))
res = samp(x0); jax.block_until_ready(res.sample)
ts = []
for _ in range(3):
    t0 = time.perf_counter(); res = samp(x0)
    jax.block_until_ready(res.sample); ts.append(time.perf_counter() - t0)
print(json.dumps({{"t": sorted(ts)[1], "iters": int(res.iterations)}}))
"""

# the (time, data, model) composition row: a reduced DiT backbone
# patch-sharded over ``model`` (K/V all-gather), batch split over
# ``data``, Parareal blocks over ``time`` — all through the one Denoiser
# seam, compared against the single-device ``srds_sample`` reference
MESH_SHAPE = (2, 2, 2)          # (time, data, model) on 8 fake devices
MESH_TOL = 5e-5                 # documented shape-dependent-gemm carve-out
MESH_CODE = r"""
import dataclasses as dc
import jax, json, time
import jax.numpy as jnp
from repro.configs.base import get_arch
from repro.configs.srds_dit import dit_denoiser
from repro.core import SRDSConfig, SolverConfig, make_schedule, srds_sample
from repro.core.pipelined import make_sharded_sampler
from repro.launch.mesh import make_srds_mesh
from repro.models.dit import init_dit

cfg = dc.replace(get_arch("srds-dit-cifar"), num_layers=2, d_model=32,
                 num_heads=2, num_kv_heads=2, head_dim=16, d_ff=64,
                 patch_size=2, dtype="float32")
params = init_dit(cfg, jax.random.PRNGKey(0))
mesh = make_srds_mesh(*{shape})
den = dit_denoiser(cfg, params, shard_axis="model", mesh=mesh,
                   use_kernel=False)
ref_fn = dit_denoiser(cfg, params, use_kernel=False)
sched = make_schedule("ddpm_linear", 8)
solver = SolverConfig("ddim")
x0 = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 8, 3))
cfg_s = SRDSConfig(num_blocks=4, per_sample=True)
ref = srds_sample(ref_fn, sched, solver, x0, cfg_s)
samp = make_sharded_sampler(mesh, "time", den, sched, solver, cfg_s,
                            data_axis="data")
res = samp(x0); jax.block_until_ready(res.sample)
diff = float(jnp.max(jnp.abs(ref.sample - res.sample)))
ts = []
for _ in range(3):
    t0 = time.perf_counter(); res = samp(x0)
    jax.block_until_ready(res.sample); ts.append(time.perf_counter() - t0)
print(json.dumps({{"t": sorted(ts)[1], "iters": int(jnp.max(res.iterations)),
                   "max_abs_diff": diff}}))
"""


def _run(code: str, devices: int):
    # the child runs on fake CPU devices: pinned to CPU so it never reaches
    # for an accelerator that this (or another) process already holds
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, env=env)
    if out.returncode != 0:
        raise RuntimeError(f"table6 subprocess ({devices} devices) failed:\n"
                           f"{out.stderr[-4000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def mesh_row():
    """The (time, data, model) DiT row for BENCH_core.json."""
    t, d, m = MESH_SHAPE
    r = _run(MESH_CODE.format(shape=MESH_SHAPE), devices=t * d * m)
    name = f"table6/mesh_t{t}d{d}m{m}"
    emit(name, r["t"] * 1e6,
         f"iters={r['iters']};max_abs_diff={r['max_abs_diff']:.2e};"
         f"within_tol={r['max_abs_diff'] <= MESH_TOL}")
    return dict(name=name, devices=t * d * m, mesh_time=t, mesh_data=d,
                mesh_model=m, iterations=r["iters"],
                max_abs_diff=r["max_abs_diff"], tol=MESH_TOL,
                within_tol=bool(r["max_abs_diff"] <= MESH_TOL),
                t_mesh_s=r["t"])


def main(out: str = None):
    for dev in (1, 2, 4):
        r = _run(CODE.format(d=dev), devices=dev)
        emit(f"table6/devices{dev}", r["t"] * 1e6,
             f"iters={r['iters']};wallclock_s={r['t']:.3f}")
    return merge_out(out, [mesh_row()], "pinned_table6",
                     {"mesh": dict(zip(("time", "data", "model"),
                                       MESH_SHAPE)),
                      "tol": MESH_TOL, "arch": "srds-dit-cifar/reduced",
                      "seed": 0, "num_steps": 8})


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None,
                    help="BENCH_core.json artifact to append the mesh "
                         "row into")
    main(out=ap.parse_args().out)
