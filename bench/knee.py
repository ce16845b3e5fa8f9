"""Find a cell's knee once, on the chip: the highest Poisson arrival rate
the served engine sustains, that is, at which the backlog does not grow.

    python3 bench/knee.py --workload cifar.poisson80 --seed 3 --seconds 20

One process builds and warms the engine once and measures a saturated
completion rate ``mu`` over a shallow backlog (``--seconds`` / 4 of it at
32 req/s, stopped after that long).  It then offers the cell's own mix
(its tiers, gap order and admission) as Poisson traffic at each of
``FRACTIONS`` of ``mu``, each for ``--seconds`` and stopped at the close.
The backlog at time t is the number of requests due by t and not yet
finished.  A rate is sustained where at most two batches are unfinished
at the close and the backlog's mean over the window's last quarter
exceeds that over the second quarter by at most two batches: near
capacity the backlog swings by tens of requests, so growth alone can pass
a rate the chip does not keep up with.  The knee is the highest rate with
every rate up to it sustained.  Each rate
prints one JSON line; the last names the knee, which is written by hand
into the traffic files, where the runs read it.  Without a TPU it exits
with code 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

FRACTIONS = (0.6, 0.7, 0.8, 0.85, 0.9, 0.95, 1.0, 1.05, 1.1, 1.2, 1.35, 1.5)


def offer(loop, policy, traffic, seed, seconds):
    """Serve ``seconds`` of ``traffic``, stopped at the close; returns
    (requests, report)."""
    from bench import harness
    from bench import traffic as gen
    reqs = gen.generate(traffic, seed, seconds)
    reqs.sort(key=lambda r: r["arrival"])
    policy.stop_at = seconds
    return reqs, loop.run(harness.to_requests(reqs))


def backlog(reqs, report, times):
    """Requests due and not finished, at each of ``times``."""
    import numpy as np
    due = np.sort([r["arrival"] for r in reqs])
    done = np.sort([r.finish_time for r in report.responses.values()
                    if r.status == "ok"])
    return (np.searchsorted(due, times, side="right")
            - np.searchsorted(done, times, side="right"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)

    import numpy as np

    from bench import harness

    cell = harness.find_cell(ROOT, args.workload)
    harness.compile_cache(BENCH)
    missing = harness.missing_chips(cell)
    if missing:
        print(f"knee: {missing}", file=sys.stderr)
        return 2
    weight_seed, _, warm_seed = harness.derive_seeds(args.seed)
    params = cell.family("references").make_params(cell.config, weight_seed)
    engine, policy, loop, _ = harness.build(cell, params)
    loop.run(harness.warm_requests(cell, warm_seed))
    print(json.dumps({"phase": "setup",
                      "setup_s": time.monotonic() - T_START}), flush=True)

    k = cell.config["engine"]["batch_size"]
    short = args.seconds / 4
    sat = dict(cell.traffic, arrivals="backlog", backlog_rps=32.0)
    _, rep = offer(loop, policy, sat, args.seed, short)
    mu = sum(1 for r in rep.responses.values()
             if r.status == "ok" and r.finish_time <= short) / short
    print(json.dumps({"phase": "saturated", "samples_per_s": mu,
                      "backlog_at_start": round(32.0 * short)}), flush=True)

    knee, below, t = None, True, np.arange(0.5, args.seconds + 1e-9, 0.5)
    for frac in FRACTIONS:
        rate = frac * mu
        tr = dict(cell.traffic, arrivals="poisson", rate_rps=rate)
        reqs, rep = offer(loop, policy, tr, args.seed + 1, args.seconds)
        q = backlog(reqs, rep, t)
        n = len(t)
        growth = float(q[3 * n // 4:].mean() - q[n // 4: n // 2].mean())
        ok = growth <= 2 * k and q[-1] <= 2 * k
        below = below and ok
        if below:
            knee = rate
        lat = sorted(r.latency for r in rep.responses.values()
                     if r.status == "ok")
        print(json.dumps({"phase": "rate", "fraction": frac,
                          "rate_rps": rate, "requests": len(reqs),
                          "unfinished_at_close": int(q[-1]),
                          "backlog_growth": growth,
                          "backlog_max": int(q.max()),
                          "p50_s": float(np.percentile(lat, 50)),
                          "p95_s": float(np.percentile(lat, 95)),
                          "sustained": ok}), flush=True)
    print(json.dumps({"phase": "knee", "knee_rps": knee,
                      "saturated_samples_per_s": mu}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
