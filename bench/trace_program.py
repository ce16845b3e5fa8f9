"""One traced run of a benchmark cell, read at the program's own spans and
scopes.

    python3 bench/trace_program.py --workload cifar.backlog --seed 7

Run from the root of a checkout, on a TPU.  The run is the harness's
``--trace 1`` run (``bench/run.py``): the cell's own traffic, its first
10 s profiled.  The last line of standard output is the harness's result
line with one more key, ``program``: what :mod:`bench.program_trace`
reads from the same profile (the step programs' device time per
refinement split by ``srds.*`` scope, the admission round's time and
scan length, the share of the idle device time inside ``serve.*`` spans,
the longest idle gaps named by the program's spans, each span's count
and time), the traced window's samples per second where the cell
reports samples per second, and the run's wall seconds.

The harness is not edited: this script wraps ``bench.tracing.load``,
which the harness calls on the profile before it removes it, and
``harness.build``, to keep the probe's dispatch count and the window's
report.  ``--seconds`` defaults to ``run_seconds`` of ``BENCHMARK.json``.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

SCOPES = ("srds.fine", "srds.coarse", "srds.correct", "srds.init", None)


def span_table(trace, program) -> dict:
    """Per span name in the window: count, mean milliseconds, total
    seconds."""
    from bench import program_trace
    out: dict = {}
    for s in program_trace.in_window(trace, program.spans):
        n, t = out.get(s.name, (0, 0.0))
        out[s.name] = (n + 1, t + s.dur_ns)
    return {k: [n, 1e-6 * t / n, 1e-9 * t] for k, (n, t) in
            sorted(out.items())}


def read(trace, program, dispatches: int) -> dict:
    """The program's view of the traced window."""
    from bench import program_trace as pt
    from bench import tracing
    dev = tracing.first_device(trace)
    step_ns = sum(e.dur_ns for e in tracing.module_events(
        trace, dev, pt.STEP_PROGRAM)) if dev else 0.0
    split = {scope or "unscoped": 1e-6 * pt.scope_ns(trace, program, dev,
                                                     scope)
             / max(dispatches, 1) for scope in SCOPES} if dev else {}
    scoped = sum(v for k, v in split.items() if k != "unscoped")
    return {
        "fine_device_ms": pt.scope_device_ms(trace, program, dispatches,
                                             "srds.fine"),
        "coarse_device_ms": pt.scope_device_ms(trace, program, dispatches,
                                               "srds.coarse"),
        "host_bound_idle_share": pt.host_bound_idle_share(trace, program),
        "admission_ms": pt.admission_ms(trace, program),
        "admission_scanned": pt.admission_scanned(trace, program),
        "step_device_ms": 1e-6 * step_ns / dispatches if dispatches
        else None,
        "step_ms_by_scope": split,
        "step_scoped_share": 100.0 * scoped * 1e6 * dispatches / step_ns
        if step_ns else None,
        "idle_in_serve_spans_share": pt.idle_in_program_share(trace,
                                                              program),
        "idle_gaps": pt.idle_gaps(trace, program),
        "spans": span_table(trace, program),
        "longest_gaps": gap_detail(trace, program),
    }


def gap_detail(trace, program, top: int = 3) -> list:
    """The ``top`` longest idle gaps of the first chip: start and length
    (ms from the window's start) and the ``serve.*`` spans overlapping
    each, outermost first, with their arguments."""
    from bench import tracing
    dev = tracing.first_device(trace)
    if dev is None:
        return []
    lo, _ = trace.window()
    out = []
    for a, b in sorted(tracing.idle_gaps(trace, dev),
                       key=lambda g: g[0] - g[1])[:top]:
        over = [[s.name, 1e-6 * (s.start_ns - lo), 1e-6 * s.dur_ns, s.args]
                for s in sorted(program.spans, key=lambda s: -s.dur_ns)
                if s.start_ns < b and s.end_ns > a][:6]
        out.append([1e-6 * (a - lo), 1e-6 * (b - a), over])
    return out


def main(argv=None, root: Path = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", str(BENCH / ".out" / "tpu_logs"))
    from bench import harness, program_trace, tracing

    cell = harness.find_cell(root, args.workload)
    seconds = args.seconds if args.seconds is not None \
        else float(cell.spec["run_seconds"])
    harness.compile_cache(cell.bench)
    missing = harness.missing_chips(cell)
    if missing:
        print(f"bench: {missing}", file=sys.stderr)
        return 2

    kept: dict = {}
    load, build = tracing.load, harness.build

    def load_both(trace_dir):
        t0 = time.monotonic()
        kept["program"] = program_trace.load(trace_dir)
        kept["program_load_s"] = time.monotonic() - t0
        kept["trace"] = load(trace_dir)
        return kept["trace"]

    def build_kept(c, params):
        engine, policy, loop, probe = build(c, params)
        run = loop.run

        def run_kept(requests):
            kept["report"] = run(requests)
            return kept["report"]

        loop.run = run_kept
        kept["probe"] = probe
        return engine, policy, loop, probe

    tracing.load, harness.build = load_both, build_kept
    try:
        out = harness.run_cell(cell, args.seed, seconds, True, T_START)
    finally:
        tracing.load, harness.build = load, build

    window = min(seconds, harness.TRACE_SECONDS)
    program = read(kept["trace"], kept["program"],
                   len(kept["probe"].dispatches)) if "trace" in kept else {}
    if cell.traffic["stop_at_window_end"]:
        done = [r for r in kept["report"].responses.values()
                if r.status == "ok" and r.finish_time <= window]
        program["samples_per_s"] = len(done) / window
    program["program_load_s"] = kept.get("program_load_s")
    program["run_s"] = time.monotonic() - T_START
    for note in out.notes:
        print(json.dumps(note), flush=True)
    print(json.dumps(dict(out.line, program=program)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
