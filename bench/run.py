"""Run one cell of ``BENCHMARK.json`` on the chip and print its result.

    python3 bench/run.py --workload cifar.poisson80 --seed 7 --seconds 20 --trace 0

Run from the root of a checkout.  The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``, read over the first 10 s of the cell's traffic),
``device``, with ``--trace 1`` a ``breakdown``, and last
``checks``: each number compared with its limit.  Earlier lines record
set-up, the window (with the number of compiles inside it, which should
be 0) and the comparison with the plain reference; the checks are also
the last lines of standard error.

Without a TPU, or with fewer chips than the cell asks for, the run exits
with code 2 and prints no result.  JAX's persistent compilation cache is
kept in ``JAX_COMPILATION_CACHE_DIR`` where that is set, else in
``bench/.jax_cache`` inside the checkout; profiles and the TPU runtime's
logs go to ``bench/.out``.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # the TPU runtime's logs stay in the checkout, not in a fixed /tmp path
    os.environ.setdefault("TPU_LOG_DIR", str(BENCH / ".out" / "tpu_logs"))
    from bench import harness

    cell = harness.find_cell(ROOT, args.workload)
    harness.compile_cache(BENCH)
    missing = harness.missing_chips(cell)
    if missing:
        print(f"bench: {missing}", file=sys.stderr)
        return 2
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           T_START)
    for note in out.notes:
        print(json.dumps(note), flush=True)
    for name, c in out.checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out.line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
