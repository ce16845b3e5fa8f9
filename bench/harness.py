"""One run of one benchmark cell, everything but the look for a chip.

The harness finds every piece by the names in ``BENCHMARK.json``:

* the configuration from the file its entry names (``bench/configs/``);
  its ``family`` names the program adapter ``bench/models/<family>.py``
  and the plain reference ``bench/references/<family>.py``;
* the traffic mix from ``bench/traffic/<traffic>.json``, read by the one
  generator in ``bench/traffic.py``;
* each per-layer metric from the reader ``bench/metrics/<name>.py``;
* the chip's peaks from ``bench/peaks.json``, by ``device_kind``.

A run builds the engine the way a user does (``AsyncServeLoop`` over
``DiffusionSamplingEngine`` on a ``MonotonicClock``), with the weights
made on the device from the seed; warms the programs the traffic reaches;
measures one window; then, with the program's state freed, compares every
sample it served with the plain reference.  The program is not edited:
the harness wraps three engine methods on the instance and serves through
the loop's ``Policy`` seam, which also stops a saturated cell at the
window's end.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import time
from pathlib import Path
from typing import Dict, List, Optional

import jax
import numpy as np

from repro.core import SolverConfig
from repro.serve.async_loop import AsyncServeLoop
from repro.serve.clock import MonotonicClock
from repro.serve.diffusion import DiffusionSamplingEngine, SampleRequest
from repro.serve.scheduler import FIFO, Policy

from bench import tracing
from bench import traffic as traffic_gen

GIB = float(1 << 30)
# the longest window a traced run profiles: its per-layer metrics are
# taken over the first this-many seconds of the cell's traffic (writing
# and reading a 40 s profile, about 100,000 device events a second, took
# a run past 360 s)
TRACE_SECONDS = 10.0


def compile_cache(bench: Path) -> str:
    """Turn on JAX's persistent compilation cache, for every program
    however short its compile: in ``JAX_COMPILATION_CACHE_DIR`` where that
    is set, else in ``<bench>/.jax_cache``, a fixed path inside the
    checkout.  Returns the directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        bench / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def missing_chips(cell: "Cell") -> Optional[str]:
    """Why this machine cannot run ``cell`` (no TPU, or fewer chips than
    the cell asks for), or ``None``."""
    devices = jax.devices()
    # the chip requirement itself, not kernel dispatch
    on_tpu = devices[0].platform == "tpu"  # reprolint: disable=RL005
    if on_tpu and len(devices) >= cell.workload["chips"]:
        return None
    return (f"the cell needs {cell.workload['chips']} TPU chip(s); JAX "
            f"found {len(devices)} {devices[0].platform!r} device(s)")


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Import the file ``path`` as a module of its own."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """A workload of ``BENCHMARK.json`` with its pieces found by name."""
    root: Path
    spec: dict
    workload: dict
    config: dict
    traffic: dict

    @property
    def name(self) -> str:
        return self.workload["name"]

    @property
    def bench(self) -> Path:
        return self.root / self.spec["paths"][0]

    def family(self, kind: str):
        fam = self.config["family"]
        return load_module(self.bench / kind / f"{fam}.py",
                           f"bench_{kind}_{fam}")

    def reports(self, entry: dict) -> bool:
        return self.name in entry.get("workloads", [self.name])

    def end_to_end(self) -> List[dict]:
        return [m for m in self.spec["end_to_end"] if self.reports(m)]

    def per_layer(self) -> List[dict]:
        return [m for m in self.spec["per_layer"] if self.reports(m)]


def find_cell(root: Path, workload: str) -> Cell:
    spec = load_json(root / "BENCHMARK.json")
    wl = [w for w in spec["workloads"] if w["name"] == workload]
    if not wl:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; have "
                       f"{[w['name'] for w in spec['workloads']]}")
    (cfg,) = [c for c in spec["configs"] if c["name"] == wl[0]["config"]]
    bench = root / spec["paths"][0]
    return Cell(root=root, spec=spec, workload=wl[0],
                config=load_json(root / cfg["file"]),
                traffic=load_json(bench / "traffic"
                                  / f"{wl[0]['traffic']}.json"))


# --------------------------------------------------------------------------
# instrumentation, from outside the program
# --------------------------------------------------------------------------

class CompileClock:
    """JAX's own compile events, counted and summed (backend compiles)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration


class Probe:
    """Host records of the loop's calls into the engine, taken by wrapping
    three methods on the engine instance; each call is also a host span
    in a profiler trace."""

    def __init__(self, engine: DiffusionSamplingEngine):
        self.engine = engine
        self.admits: Dict[int, float] = {}
        self.dispatches: List[float] = []     # clock of each refinement
        self.resolves: List[tuple] = []        # (clock, effective, physical)
        admit, dispatch = engine.admit, engine.step_dispatch
        resolve = engine.step_resolve

        def _admit(rid, req):
            with jax.profiler.TraceAnnotation("bench.admit"):
                self.admits[rid] = engine.clock
                return admit(rid, req)

        def _dispatch(*a, **kw):
            with jax.profiler.TraceAnnotation("bench.step_dispatch"):
                tok = dispatch(*a, **kw)
            if tok is not None:
                self.dispatches.append(engine.clock)
            return tok

        def _resolve(tok):
            e0, p0 = engine.effective_evals, engine.physical_evals
            with jax.profiler.TraceAnnotation("bench.step_resolve"):
                out = resolve(tok)
            self.resolves.append((engine.clock, engine.effective_evals - e0,
                                  engine.physical_evals - p0))
            return out

        engine.admit, engine.step_dispatch = _admit, _dispatch
        engine.step_resolve = _resolve

    def reset(self):
        self.admits, self.dispatches, self.resolves = {}, [], []


class WindowPolicy(Policy):
    """The traffic's admission policy, timed as a host span; with
    ``stop_at`` set, from then on it evicts what runs and turns away what
    waits, which ends the loop: the saturated cell's window close."""

    def __init__(self, inner: Policy):
        self.inner = inner
        self.name = inner.name
        self.stop_at: Optional[float] = None
        self.stopped_running: List[int] = []
        self.stopped_queued: List[int] = []

    def _stopped(self, now: float) -> bool:
        return self.stop_at is not None and now >= self.stop_at

    def select(self, now, queue, engine):
        with jax.profiler.TraceAnnotation("bench.policy"):
            return self.inner.select(now, queue, engine)

    def reject(self, now, rid, req, engine):
        if self._stopped(now):
            self.stopped_queued.append(rid)
            return True
        return self.inner.reject(now, rid, req, engine)

    def preempt_victims(self, now, running, queue, engine):
        if self._stopped(now):
            victims = [rid for rid, _ in running]
            self.stopped_running += victims
            return victims
        return self.inner.preempt_victims(now, running, queue, engine)


POLICIES = {"fifo": FIFO}


# --------------------------------------------------------------------------
# what a metric reader sees
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """Everything one measured window left behind, for the metric readers."""
    cell: Cell
    seconds: float
    requests: List[dict]                 # as generated, rid order
    responses: Dict[int, object]         # rid -> SampleResponse
    probe: Probe
    peak: dict                           # the chip's row of peaks.json
    trace: Optional[tracing.Trace] = None

    @property
    def config(self) -> dict:
        return self.cell.config

    def resolves_in_window(self):
        return [r for r in self.probe.resolves if r[0] <= self.seconds]

    def trace_window_s(self) -> Optional[float]:
        if self.trace is None:
            return None
        lo, hi = self.trace.window()
        return (hi - lo) * 1e-9


def nearest_rank(values: List[float], q: float) -> float:
    """The ``q`` quantile by nearest rank (a missing value is ``inf``)."""
    v = sorted(values)
    return v[max(0, math.ceil(q * len(v)) - 1)]


# --------------------------------------------------------------------------
# a run
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Outcome:
    notes: List[dict]                    # earlier stdout lines
    line: dict                           # the last stdout line
    checks: Dict[str, dict]              # numbers compared, with limits


def build(cell: Cell, params):
    cfg, eng = cell.config, cell.config["engine"]
    model_fn = cell.family("models").build_denoiser(cfg, params)
    ref = cell.family("references")
    engine = DiffusionSamplingEngine(
        model_fn, ref.sample_shape(cfg), SolverConfig(eng["solver"]),
        schedule=eng["schedule"], num_steps=eng["num_steps"],
        batch_size=eng["batch_size"], num_blocks=eng["num_blocks"],
        norm=eng["norm"], clock=MonotonicClock())
    policy = WindowPolicy(POLICIES[cell.traffic["admission"]]())
    loop = AsyncServeLoop(engine, policy, max_inflight=eng["max_inflight"])
    return engine, policy, loop, Probe(engine)


def derive_seeds(seed: int):
    """The run's seeds for its weights, its traffic and its warm-up, drawn
    from ``--seed`` (any whole number)."""
    rng = np.random.default_rng(seed)
    return [int(x) for x in rng.integers(0, 2 ** 31 - 1, 3)]


def warm_requests(cell: Cell, seed: int) -> List[SampleRequest]:
    """The warm-up: a full batch run to every refinement (``tol=0``), which
    steps through each frontier the traffic can reach, then two batches of
    the traffic's own tiers admitted into running slots."""
    k = cell.config["engine"]["batch_size"]
    n = cell.traffic["num_steps"]
    tols = [0.0] * k + [t["tol"] for t in cell.traffic["tiers"]] * k
    seeds = np.random.default_rng(seed).integers(0, 2 ** 31 - 1, len(tols))
    return [SampleRequest(seed=int(s), tol=t, num_steps=n)
            for s, t in zip(seeds, tols)]


def to_requests(reqs: List[dict]) -> List[SampleRequest]:
    """The generator's requests as the engine takes them."""
    return [SampleRequest(seed=r["noise_seed"], tol=r["tol"],
                          num_steps=r["num_steps"], arrival_time=r["arrival"])
            for r in reqs]


def rel_l2(got: np.ndarray, want: np.ndarray) -> List[float]:
    """Per sample: the L2 norm of the difference over the reference's."""
    return [float(np.linalg.norm(g - w) / np.linalg.norm(w))
            for g, w in zip(got, want)]


def judge(got: np.ndarray, want: np.ndarray, due: int, limit: float):
    """The comparison that decides ``correct``: the samples in the
    program's place (``got``) against the reference's for the same
    requests (``want``), by the worst relative L2 error, and at least
    ``due`` of them served.  Returns ``(correct, checks)``, each number
    compared beside its limit."""
    rel = rel_l2(got, want)
    worst = max(rel) if rel and np.all(np.isfinite(got)) else math.inf
    checks = {"sample_rel_l2_max": {"value": worst, "limit": limit},
              "served_ok": {"value": len(got), "limit": due}}
    return bool(rel and worst <= limit and len(got) >= due), checks


def window_traffic(cell: Cell, seed: int, seconds: float, trace: bool):
    """The window's requests in the loop's rid order, and the length of
    the window.  A traced run keeps the cell's traffic as it is (the same
    rate, the same backlog) and serves its first ``TRACE_SECONDS``: the
    requests due by then, or, for a backlog, all of them until then."""
    reqs = traffic_gen.generate(cell.traffic, seed, seconds)
    reqs.sort(key=lambda r: r["arrival"])          # rid order of the loop
    if not trace or seconds <= TRACE_SECONDS:
        return reqs, seconds
    if not cell.traffic["stop_at_window_end"]:
        reqs = [r for r in reqs if r["arrival"] < TRACE_SECONDS]
    return reqs, TRACE_SECONDS


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, control: bool = False) -> Outcome:
    """One run: set-up, the measured window, the comparison with the
    reference and, with ``trace``, the per-layer metrics of the window's
    profile.  ``t_start`` is the process's start on the monotonic clock.
    With ``control``, the reference computed one precision step below the
    configuration's is put in the program's place for the comparison, on
    the requests this run served; the program's own reading is an earlier
    note."""
    cfg = cell.config
    ref = cell.family("references")
    dev = jax.devices()[0]
    peaks = load_json(cell.bench / "peaks.json")
    if dev.device_kind not in peaks:
        raise KeyError(f"no peaks for device kind {dev.device_kind!r} in "
                       f"peaks.json; have {sorted(peaks)}")
    clock = CompileClock()
    weight_seed, traffic_seed, warm_seed = derive_seeds(seed)

    params = ref.make_params(cfg, weight_seed)
    engine, policy, loop, probe = build(cell, params)
    loop.run(warm_requests(cell, warm_seed))
    setup_compiles = clock.count

    reqs, seconds = window_traffic(cell, traffic_seed, seconds, trace)
    served = to_requests(reqs)
    probe.reset()
    stop = cell.traffic["stop_at_window_end"]
    policy.stop_at = seconds if stop else None
    trace_dir = cell.bench / ".out" / "trace"
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        # host spans and device events only: the Python call tracer
        # would slow the host it measures and swell the trace
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    setup_s = time.monotonic() - t_start
    c0 = clock.count
    with jax.profiler.TraceAnnotation("bench.window"):
        w0 = time.monotonic()
        report = loop.run(served)
        window_s = time.monotonic() - w0
    window_compiles = clock.count - c0
    if trace:
        jax.profiler.stop_trace()
    stats = dev.memory_stats() or {}
    peak_bytes = int(stats.get("peak_bytes_in_use", 0))

    run = Run(cell=cell, seconds=seconds, requests=reqs,
              responses=dict(report.responses), probe=probe,
              peak=peaks[dev.device_kind])
    notes = [{"phase": "setup", "workload": cell.name, "seed": seed,
              "weight_seed": weight_seed, "requests": len(reqs),
              "setup_compiles": setup_compiles,
              "compile_s": clock.seconds, "setup_s": setup_s},
             {"phase": "window", "compiles_in_window": window_compiles,
              "window_s": window_s, "completed": len(report.responses),
              "refinements_mean": float(np.mean(
                  [r.iterations for r in report.responses.values()]))
              if report.responses else None,
              "stopped_running": len(policy.stopped_running),
              "stopped_queued": len(policy.stopped_queued),
              "rejected": len(report.rejected),
              "preempted": len(report.preempted)}]

    # the program's state goes before the reference runs on the chip
    del engine, loop, policy
    probe.engine = None
    gc.collect()
    jax.clear_caches()

    ok = {rid: r for rid, r in run.responses.items()
          if r.status == "ok" and r.sample is not None}
    compared = sorted(ok)
    seeds = [reqs[i]["noise_seed"] for i in compared]
    n_steps = cell.traffic["num_steps"]
    t_ref = time.monotonic()
    want = ref.solve_all(cfg, params, seeds, n_steps)
    got = np.stack([ok[i].sample for i in compared]) if compared \
        else want[:0]
    rel = rel_l2(got, want)
    notes.append({"phase": "reference", "compared": len(compared),
                  "reference_s": time.monotonic() - t_ref,
                  "sample_rel_l2_median": float(np.median(rel)) if rel
                  else None,
                  "by_tier_max": {str(t): max([x for i, x in zip(compared, rel)
                                               if reqs[i]["tol"] == t],
                                              default=None)
                                  for t in sorted({r["tol"] for r in reqs})}})

    # open-loop traffic is all due; a stopped backlog owes what it served
    due = len(ok) if stop else len(reqs)
    limit = cfg["limits"]["sample_rel_l2_max"]
    correct, checks = judge(got, want, due, limit)
    if control:
        notes.append({"phase": "program", "correct": correct,
                      "checks": checks})
        got = ref.solve_all(cfg, params, seeds, n_steps, ref.CONTROL)
        correct, checks = judge(got, want, due, limit)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak_bytes}
    line = {"correct": correct, "attempted": due,
            "failed": due - len(ok)}
    if trace:
        run.trace = tracing.load(str(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        devs = tracing.devices(run.trace)
        busy = [tracing.busy_ns(run.trace, d) * 1e-9 for d in devs]
        device["busy_s"] = float(np.mean(busy)) if busy else 0.0
        device["window_s"] = run.trace_window_s()
        metrics = {}
        for m in cell.per_layer():
            reader = load_module(cell.bench / "metrics" / f"{m['name']}.py",
                                 f"bench_metric_{m['name']}")
            value = reader.read(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        line["metrics"] = metrics
        line["device"] = device
        line["breakdown"] = tracing.breakdown(run.trace)
    else:
        e2e = end_to_end(run, setup_s, peak_bytes)
        line["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                       "unit": m["unit"]}
                           for m in cell.end_to_end()}
        line["device"] = device
    line["checks"] = checks
    return Outcome(notes=notes, line=line, checks=checks)


def end_to_end(run: Run, setup_s: float, peak_bytes: int) -> Dict[str, float]:
    """The end-to-end metrics, over all the window's work."""
    out = {"setup_s": setup_s, "hbm_peak_gib": peak_bytes / GIB}
    if run.cell.traffic["stop_at_window_end"]:
        done = [r for r in run.responses.values()
                if r.status == "ok" and r.finish_time <= run.seconds]
        out["samples_per_s"] = len(done) / run.seconds
    else:
        lats = [run.responses[i].latency
                if i in run.responses and run.responses[i].status == "ok"
                else math.inf for i in range(len(run.requests))]
        out["latency_p50_s"] = nearest_rank(lats, 0.50)
        out["latency_p95_s"] = nearest_rank(lats, 0.95)
    return out
