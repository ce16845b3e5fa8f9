"""Run every paper-table benchmark.  One function per paper table.
Prints ``name,us_per_call,derived`` CSV lines."""
import sys
import time

from . import (prop4_blocksize, table1_pixel, table2_sd, table3_pipelined,
               table4_paradigms, table5_solvers, table6_devices,
               table8_tolerance, table9_batched, table10_slo,
               table11_truncation)

TABLES = [
    ("table1 (pixel diffusion, N=1024)", table1_pixel.main),
    ("table2 (SD-like latent, vanilla SRDS)", table2_sd.main),
    ("table3 (pipelined SRDS)", table3_pipelined.main),
    ("table4 (vs ParaDiGMS)", table4_paradigms.main),
    ("table5 (other solvers)", table5_solvers.main),
    ("table6 (device scaling)", table6_devices.main),
    ("table8 (tolerance ablation)", table8_tolerance.main),
    ("table9 (batched serving)", table9_batched.main),
    ("table10 (SLO scheduling)", table10_slo.main),
    ("table11 (prefix truncation)", table11_truncation.main),
    ("prop4 (block-size optimum)", prop4_blocksize.main),
]


def main() -> None:
    print("name,us_per_call,derived")
    failed = []
    for title, fn in TABLES:
        print(f"# --- {title} ---", flush=True)
        t0 = time.time()
        try:
            fn()
        except Exception as e:  # keep the suite going; report the failure
            print(f"{title},-1,FAILED:{type(e).__name__}:{e}", flush=True)
            failed.append(title)
        print(f"# {title} done in {time.time() - t0:.0f}s", flush=True)
    if failed:
        sys.exit(f"{len(failed)} table(s) failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
