"""Readings that set a cell's correctness limit, on the chip.

    python chipbench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 15

One process (set-up is long, and only one process may hold the chip): for
each seed, a whole run of the cell at its own size and load with a short
window. After the window the plain reference runs over the run's sample,
and beside it the configuration's lower-precision control (``control`` in
the configuration file: int4 weights in place of int8) is put in the
program's place at the same positions (``harness.cell.run``). Prints, per
seed, the program's widest served-token gap (the lower reading) and
whether that run is correct, the control's widest gap (the upper reading)
and whether the control comes out correct under the cell's limit, and
last a JSON object with all of them. The benchmark's own runs never run
the control.
"""
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
sys.path.insert(0, HERE)


def main() -> int:
    import argparse
    import gc
    import json

    from harness import cell, spec

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    a = ap.parse_args()
    bench = spec.load_benchmark()
    cfg = spec.config(bench, spec.workload(bench, a.workload)["config"])
    rows = []
    for s in (int(x) for x in a.seeds.split(",")):
        t0 = time.perf_counter()
        try:
            out = cell.run(bench, a.workload, s, a.seconds, False, t0,
                           control=cfg["control"])
        except cell.NoChip as e:
            print(f"calibrate: {e}", file=sys.stderr)
            return 2
        row = {"seed": s, "gap": out["program_gap"],
               "correct": out["program_correct"],
               "control_gap": out["checks"]["logit_gap"]["value"],
               "control_correct": out["correct"],
               "limit": out["checks"]["logit_gap"]["limit"],
               "attempted": out["attempted"],
               "failed": out["failed"],
               "metrics": {k: v["value"] for k, v in out["metrics"].items()},
               "seconds": time.perf_counter() - t0}
        rows.append(row)
        print(json.dumps(row), flush=True)
        gc.collect()
    print(json.dumps({"workload": a.workload, "control": cfg["control"],
                      "runs": rows}), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    os._exit(rc)
