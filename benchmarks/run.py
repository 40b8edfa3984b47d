"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows on stdout (detailed per-figure
tables as '#' comment lines above each block).
"""
from __future__ import annotations

import argparse
import sys
import time


MODULES = [
    "fig2_variant_space",
    "fig8_latency_fit",
    "fig15_overhead",
    "fig3_replication_batching",
    "fig5_colocation",
    "fig10_online_offline",
    "fig11_autoscaling",
    "fig13_realistic",
    "roofline_table",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated module substring filter")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    mods = MODULES
    if args.only:
        keys = args.only.split(",")
        mods = [m for m in MODULES if any(k in m for k in keys)]
    print("name,us_per_call,derived")
    for name in mods:
        mod = __import__(f"benchmarks.{name}", fromlist=["run"])
        t0 = time.time()
        try:
            rows = mod.run(verbose=True)
        except Exception as e:  # noqa: BLE001 - report and continue
            print(f"# {name} FAILED: {type(e).__name__}: {e}",
                  file=sys.stderr)
            print(f"{name}_FAILED,0,{type(e).__name__}")
            continue
        dt = time.time() - t0
        print(f"# [{name} took {dt:.1f}s]")
        for row_name, val, derived in rows:
            print(f"{row_name},{val:.6g},{derived}")
        sys.stdout.flush()


if __name__ == "__main__":
    main()
