"""The control of the comparison that decides ``correct``: whole runs of
a cell in which, after the window, the plain reference computed one
precision step below the configuration's bf16 (float8_e4m3fn matmul
operands, per-tensor scaled) is put in the program's place, on the
requests the run served, and judged by the harness's own comparison.

    python3 bench/control.py --workload cifar.poisson80 --seconds 40 --seeds 11 12 13

Each seed prints the run's notes (the program's own reading among them)
and then the harness's result line, whose ``correct`` has to be false.
The benchmark's own runs do not run it.  Without a TPU it exits with
code 2 and prints no result.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    from bench import harness
    cell = harness.find_cell(ROOT, args.workload)
    harness.compile_cache(BENCH)
    missing = harness.missing_chips(cell)
    if missing:
        print(f"control: {missing}", file=sys.stderr)
        return 2
    t_start = T_START
    for seed in args.seeds:
        out = harness.run_cell(cell, seed, args.seconds, False, t_start,
                               control=True)
        for note in out.notes:
            print(json.dumps(note), flush=True)
        print(json.dumps(out.line), flush=True)
        t_start = time.monotonic()
    return 0


if __name__ == "__main__":
    sys.exit(main())
