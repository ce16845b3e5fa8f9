"""Chip smoke test: the served SRDS path at the full width of
``srds-dit-cifar`` (12 layers, d_model 768, 12 heads, patch 4, 32x32x3,
bf16 weights), run on a TPU through the entry points a user calls.

    python3 chip_smoke.py              # one chip: serve + correctness checks
    python3 chip_smoke.py --chips 4    # four chips: time-parallel engine only

The weights are random, drawn from ``--seed``: ``init_dit`` zero-initialises
the adaLN modulation and the output projection (a DiT with those weights
returns exactly zero), so those leaves are redrawn as small normals and
every layer reaches the output.

One chip: ``DiffusionSamplingEngine`` (25-step DDIM grid, 4 slots, its
defaults: ExactPrefix truncation, the fused Pallas corrector where the
backend compiles it, donated trajectory buffers) on a ``MonotonicClock``,
driven by ``AsyncServeLoop`` with 8 seeded requests in two tolerance
tiers.  Then, all on the chip: every sample is finite; each served sample
against ``srds_sample`` of the same request (and against ``srds_sample``
of the request in every slot, the batch shape the engine evaluates); a
``tol=0`` request (B refinements) against ``sample_sequential``; a
``use_fused=False`` engine against the fused one, bit for bit; and one
DiT forward through the Pallas flash kernel against XLA attention, with
``tpu_custom_call`` in its compiled text.

``--chips 4``: the same requests through an engine whose fine solves
shard over a 4-device ``time`` mesh axis (100 steps, 20 blocks), against
a one-chip ``truncate=False`` engine, with evidence that all four devices
hold shards and join the all-gather.

Each phase prints one JSON line.  The last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Without a TPU the script exits non-zero and prints no result; a failed
check raises, and the exit code is non-zero.  The phase functions take
the model and sizes as arguments, so tests drive them on a small DiT on
the CPU.
"""
from __future__ import annotations

import argparse
import functools
import json
import re
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.base import get_arch  # noqa: E402
from repro.configs.srds_dit import dit_denoiser  # noqa: E402
from repro.core import (SolverConfig, SRDSConfig, make_schedule,  # noqa: E402
                        resolve_blocks, sample_sequential, srds_sample)
from repro.launch.env import configure_compile_cache  # noqa: E402
from repro.launch.mesh import make_srds_mesh  # noqa: E402
from repro.models.dit import dit_forward, init_dit  # noqa: E402
from repro.serve.async_loop import AsyncServeLoop  # noqa: E402
from repro.serve.clock import MonotonicClock  # noqa: E402
from repro.serve.diffusion import (DiffusionSamplingEngine,  # noqa: E402
                                   SampleRequest)

ARCH = "srds-dit-cifar"
SHAPE = (32, 32, 3)
NUM_STEPS = 25            # the paper's SD-v2 headline grid
BATCH = 4                 # engine slots
TIERS = (1e-2, 1e-3)      # the two tolerance tiers (l1_mean residual)
N_REQUESTS = 8
LIVE_STD = 0.02           # std of the redrawn adaLN / output leaves
SOLVER = SolverConfig("ddim")
# the four-chip phase: time-parallel fine solves over 4 devices
TP_NUM_STEPS, TP_NUM_BLOCKS = 100, 20

# Bounds, as max|a - b| / max|b| over the compared samples.  Paths that
# agree up to shape-dependent gemm roundoff (the engine's documented
# carve-out for matmul denoisers: a different batch composition can pick
# a different bf16 gemm tiling) stay below ROUNDOFF_BOUND; bitwise
# agreement is reported beside it.  ATTN_BOUND is the bf16 flash kernel
# (f32 online softmax) against XLA's f32-softmax attention after one
# full forward.
ROUNDOFF_BOUND = 2e-2
ATTN_BOUND = 2e-2


class SmokeFailure(RuntimeError):
    """A phase's check did not hold."""


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


# --------------------------------------------------------------------------
# model and traffic
# --------------------------------------------------------------------------

def live_params(cfg, seed: int):
    """``init_dit`` weights with the zero-initialised adaLN modulation
    (``mod``/``mod_b``/``mod_f``/``mod_fb``) and output projection
    (``patch_out``) redrawn as small normals, so the DiT's output depends
    on every layer."""
    params = init_dit(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(
        jax.random.fold_in(jax.random.PRNGKey(seed), 1), 5))

    def draw(a):
        z = jax.random.normal(next(keys), a.shape, jnp.float32)
        return (LIVE_STD * z).astype(a.dtype)

    blocks = dict(params["blocks"], mod=draw(params["blocks"]["mod"]),
                  mod_b=draw(params["blocks"]["mod_b"]))
    return dict(params, blocks=blocks, mod_f=draw(params["mod_f"]),
                mod_fb=draw(params["mod_fb"]),
                patch_out=draw(params["patch_out"]))


def make_requests(seed: int, n: int = N_REQUESTS, tiers=TIERS):
    """``n`` requests arriving together, alternating the tolerance tiers."""
    return [SampleRequest(seed=seed * 1000 + i, tol=tiers[i % len(tiers)])
            for i in range(n)]


def x_init(req: SampleRequest, shape=SHAPE):
    """The engine's own draw of a request's initial noise."""
    return jax.random.normal(jax.random.PRNGKey(req.seed), shape,
                             jnp.float32)


def rel_diff(a, b) -> dict:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    d = float(np.max(np.abs(a - b)))
    scale = float(np.max(np.abs(b)))
    return {"max_abs_diff": d, "ref_max_abs": scale,
            "rel": d / scale if scale else d, "bitwise": bool(d == 0.0)}


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------

def check_live(cfg, params, shape=SHAPE) -> dict:
    """One forward on seeded noise: the output must not be identically 0."""
    x = jax.random.normal(jax.random.PRNGKey(7), (2,) + shape, jnp.float32)
    t = jnp.full((2,), 500.0, jnp.float32)
    eps = jax.jit(functools.partial(dit_forward, cfg, use_kernel=False))(
        params, x, t)
    m = float(jnp.max(jnp.abs(eps)))
    require(m > 0.0 and np.isfinite(m), f"DiT output max|eps| = {m}")
    return {"max_abs_eps": m}


def serve(model_fn, reqs, *, shape=SHAPE, num_steps=NUM_STEPS, **engine_kw):
    """Serve ``reqs`` twice through ``AsyncServeLoop`` on a fresh engine:
    a cold run (compiles) and a warm one.  Returns ``(engine, cold, warm,
    cold_s, warm_s)`` with the two ``SimReport``s and their wall seconds;
    every request must complete in both runs with a finite sample."""
    eng = DiffusionSamplingEngine(model_fn, shape, SOLVER,
                                  num_steps=num_steps, batch_size=BATCH,
                                  clock=MonotonicClock(), **engine_kw)
    loop = AsyncServeLoop(eng)
    runs = []
    for _ in range(2):
        t0 = time.perf_counter()
        rep = loop.run(reqs)
        runs.append((rep, time.perf_counter() - t0))
        require(len(rep.responses) == len(reqs) and not rep.rejected
                and not rep.preempted,
                f"{len(rep.responses)}/{len(reqs)} requests completed "
                f"(rejected {rep.rejected}, preempted {rep.preempted})")
        for rid, resp in rep.responses.items():
            require(resp.status == "ok" and resp.sample is not None
                    and bool(np.all(np.isfinite(resp.sample))),
                    f"request {rid}: status {resp.status}, non-finite sample")
    (cold, cold_s), (warm, warm_s) = runs
    return eng, cold, warm, cold_s, warm_s


def samples(report, n: int) -> np.ndarray:
    """The report's samples in submission order (rids are 0..n-1)."""
    return np.stack([report.responses[i].sample for i in range(n)])


def iterations(report, n: int):
    return [int(report.responses[i].iterations) for i in range(n)]


def against_srds_sample(model_fn, reqs, report, *, shape=SHAPE,
                        num_steps=NUM_STEPS, lanes=BATCH) -> dict:
    """Each served sample against a standalone ``srds_sample`` of the same
    request (one program, the tolerance traced): over all requests, and
    over those that ran as many refinements as the reference.

    ``k_lane_witness`` runs ``srds_sample`` on ``lanes`` copies of the
    request, so every model eval has the shape of the engine's slot
    batch; where the served samples match it but not the one-lane
    reference, the difference comes from the batch shape of the evals."""
    sched = make_schedule("ddpm_linear", num_steps)
    ref = jax.jit(lambda x, tol: srds_sample(model_fn, sched, SOLVER, x[None],
                                             SRDSConfig(), tol=tol))
    wit = jax.jit(lambda x, tol: srds_sample(
        model_fn, sched, SOLVER, jnp.broadcast_to(x, (lanes,) + x.shape),
        SRDSConfig(per_sample=True), tol=tol))
    got = samples(report, len(reqs))
    got_iters = iterations(report, len(reqs))
    outs = [ref(x_init(r, shape), jnp.float32(r.tol)) for r in reqs]
    want = np.stack([np.asarray(o.sample[0]) for o in outs])
    res = rel_diff(got, want)
    res["ref_iterations"] = [int(o.iterations) for o in outs]
    res["iterations_equal"] = got_iters == res["ref_iterations"]
    same = [i for i, k in enumerate(got_iters)
            if k == res["ref_iterations"][i]]
    res["equal_iterations"] = dict(
        requests=same, **(rel_diff(got[same], want[same]) if same else {}))
    outs = [wit(x_init(r, shape), jnp.float32(r.tol)) for r in reqs]
    w = rel_diff(got, np.stack([np.asarray(o.sample[0]) for o in outs]))
    w["iterations"] = [int(o.iterations[0]) for o in outs]
    w["iterations_equal"] = got_iters == w["iterations"]
    res["k_lane_witness"] = w
    require(res["rel"] <= ROUNDOFF_BOUND,
            f"served vs srds_sample: {res}")
    return res


def converged_vs_sequential(model_fn, engine, seed: int, *, shape=SHAPE,
                            num_steps=NUM_STEPS) -> dict:
    """A ``tol=0`` request served to full convergence (B refinements)
    against the plain sequential solve."""
    req = SampleRequest(seed=seed * 1000 + 999, tol=0.0)
    rep = AsyncServeLoop(engine).run([req])
    resp = rep.responses[0]
    B, _ = resolve_blocks(num_steps, engine.num_blocks)
    require(resp.iterations == B,
            f"tol=0 request ran {resp.iterations} refinements, want B={B}")
    sched = make_schedule("ddpm_linear", num_steps)
    seq = jax.jit(lambda x: sample_sequential(model_fn, sched, SOLVER,
                                              x[None]))(x_init(req, shape))
    res = rel_diff(resp.sample, np.asarray(seq[0]))
    res["iterations"] = int(resp.iterations)
    require(res["rel"] <= ROUNDOFF_BOUND,
            f"converged vs sample_sequential: {res}")
    return res


def fused_vs_reference(model_fn, reqs, fused_report, *, shape=SHAPE,
                       num_steps=NUM_STEPS) -> dict:
    """A ``use_fused=False`` engine (the jnp corrector) on the same
    requests, against the default engine's samples: both run the same
    model evals, so the samples and iteration counts must be equal."""
    _, _, warm, _, _ = serve(model_fn, reqs, shape=shape,
                             num_steps=num_steps, use_fused=False)
    res = rel_diff(samples(fused_report, len(reqs)), samples(warm, len(reqs)))
    res["iterations_equal"] = iterations(fused_report, len(reqs)) == \
        iterations(warm, len(reqs))
    require(res["bitwise"] and res["iterations_equal"],
            f"fused vs reference: {res}")
    return res


def flash_vs_xla(cfg, params, *, shape=SHAPE) -> dict:
    """One DiT forward with the flash kernel against one with XLA
    attention; the weights are arguments, so the compiled text is the
    program's own.  Returns the difference and whether the kernel's
    compiled text holds a ``tpu_custom_call`` (the Mosaic kernel)."""
    x = jax.random.normal(jax.random.PRNGKey(11), (BATCH,) + shape,
                          jnp.float32)
    t = jnp.full((BATCH,), 500.0, jnp.float32)
    kern = jax.jit(functools.partial(dit_forward, cfg, use_kernel=True))
    compiled = kern.lower(params, x, t).compile()
    got = compiled(params, x, t)
    want = jax.jit(functools.partial(dit_forward, cfg, use_kernel=False))(
        params, x, t)
    res = rel_diff(got, want)
    res["tpu_custom_call"] = "tpu_custom_call" in compiled.as_text()
    require(res["rel"] <= ATTN_BOUND, f"flash vs XLA attention: {res}")
    return res


def collective_group_sizes(hlo: str) -> dict:
    """``{op: [replica-group sizes]}`` of the collectives in an HLO text."""
    out: dict = {}
    pat = re.compile(r"\b(all-gather|all-reduce|collective-permute|"
                     r"all-to-all)(?:-start)?\(")
    for line in hlo.splitlines():
        m = pat.search(line)
        if not m:
            continue
        g = re.search(r"replica_groups=\{\{([\d,]+)\}", line)
        if g:
            size = len(g.group(1).split(","))
        else:
            g = re.search(r"replica_groups=\[\d+,(\d+)\]<=", line)
            size = int(g.group(1)) if g else 0
        out.setdefault(m.group(1), []).append(size)
    return out


def time_parallel(model_fn, reqs, devices, *, shape=SHAPE,
                  num_steps=TP_NUM_STEPS, num_blocks=TP_NUM_BLOCKS) -> dict:
    """Serve ``reqs`` with the fine solves sharded over a ``time`` mesh
    axis of ``devices``, and with a one-chip ``truncate=False`` engine
    (the axis engine degrades to FixedBudget, so both run the same
    schedule).  Returns the comparison and the evidence that every device
    holds shards and joins the block all-gather."""
    n_dev = len(devices)
    mesh = make_srds_mesh(time=n_dev, devices=devices)
    eng, _, warm, cold_s, warm_s = serve(
        model_fn, reqs, shape=shape, num_steps=num_steps,
        num_blocks=num_blocks, mesh=mesh, axis="time")
    _, _, ref, ref_cold_s, ref_warm_s = serve(
        model_fn, reqs, shape=shape, num_steps=num_steps,
        num_blocks=num_blocks, truncate=False)
    res = rel_diff(samples(warm, len(reqs)), samples(ref, len(reqs)))
    res["iterations"] = iterations(warm, len(reqs))
    res["iterations_equal"] = res["iterations"] == iterations(ref, len(reqs))
    res.update(mesh_cold_s=cold_s, mesh_warm_s=warm_s,
               one_chip_cold_s=ref_cold_s, one_chip_warm_s=ref_warm_s)
    require(res["rel"] <= ROUNDOFF_BOUND, f"{n_dev}-device vs one: {res}")

    # the trajectory the engine keeps between refinements, and the step
    # program that produced it
    (batch,) = eng._batches.values()
    held = {d.id for d in batch.x_tail.sharding.device_set}
    step = batch.step_for(0).lower(
        eng.params, batch.x_init, batch.x_tail, batch.prev_coarse,
        jnp.asarray(batch.active)).compile()
    groups = collective_group_sizes(step.as_text())
    res.update(trajectory_devices=sorted(held), collectives=groups)
    require(held == {d.id for d in devices},
            f"trajectory held on devices {sorted(held)}")
    require(n_dev in groups.get("all-gather", []),
            f"no all-gather across {n_dev} devices: {groups}")
    stats = [d.memory_stats() for d in devices]
    if all(s is not None for s in stats):
        res["bytes_in_use"] = [int(s["bytes_in_use"]) for s in stats]
        res["peak_bytes_in_use"] = [int(s["peak_bytes_in_use"])
                                    for s in stats]
        require(all(b > 0 for b in res["bytes_in_use"]),
                f"a device holds nothing: {res['bytes_in_use']}")
    return res


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, summed from its
    own monitoring events (registered once per process)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration


# --------------------------------------------------------------------------
# entry point
# --------------------------------------------------------------------------

def one_chip(cfg, params, seed: int, clock: CompileClock) -> None:
    model_fn = dit_denoiser(cfg, params)
    reqs = make_requests(seed)
    c0 = clock.seconds
    eng, cold, warm, cold_s, warm_s = serve(model_fn, reqs)
    emit("serve", requests_completed=len(warm.responses),
         use_fused=eng.use_fused, truncate=eng.truncate,
         donated_args=list(eng._donate),
         cold_run_s=cold_s, cold_compile_s=clock.seconds - c0,
         first_request_latency_cold_s=cold.responses[0].latency,
         first_request_latency_s=warm.responses[0].latency,
         latency_p50_s=warm.latency_p50, latency_p95_s=warm.latency_p95,
         warm_run_s=warm_s,
         srds_iterations=iterations(warm, len(reqs)),
         tolerances=[r.tol for r in reqs])
    emit("served_vs_srds_sample",
         **against_srds_sample(model_fn, reqs, warm))
    emit("converged_vs_sequential",
         **converged_vs_sequential(model_fn, eng, seed))
    emit("fused_vs_reference", **fused_vs_reference(model_fn, reqs, warm))
    res = flash_vs_xla(cfg, params)
    require(res["tpu_custom_call"],
            "flash forward's compiled text has no tpu_custom_call")
    emit("flash_vs_xla", **res)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the time-parallel four-chip phase")
    args = ap.parse_args(argv)

    cache_dir = configure_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    # the chip requirement itself, not fused-path dispatch
    if dev.platform != "tpu":  # reprolint: disable=RL005
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r}); "
              "refusing to run", file=sys.stderr)
        return 2
    require(len(devices) >= args.chips,
            f"--chips {args.chips} but JAX sees {len(devices)} device(s)")
    clock = CompileClock()
    cfg = get_arch(ARCH)
    emit("setup", arch=ARCH, num_layers=cfg.num_layers,
         d_model=cfg.d_model, num_heads=cfg.num_heads,
         patch_size=cfg.patch_size, dtype=cfg.dtype, shape=list(SHAPE),
         seed=args.seed, compile_cache=cache_dir, jax=jax.__version__)
    params = live_params(cfg, args.seed)
    emit("live_weights", **check_live(cfg, params))

    if args.chips == 4:
        c0 = clock.seconds
        res = time_parallel(dit_denoiser(cfg, params),
                            make_requests(args.seed), devices[:4])
        emit("time_parallel", compile_s=clock.seconds - c0, **res)
    else:
        one_chip(cfg, params, args.seed, clock)

    stats = dev.memory_stats() or {}
    emit("memory", peak_bytes_in_use=stats.get("peak_bytes_in_use"),
         compile_s_total=clock.seconds)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
