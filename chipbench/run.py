"""Chip benchmark entry point.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` in this process on the chips JAX
finds here (it starts no other process that touches JAX), and prints one
JSON object as the last line of standard output. With no TPU, or fewer
chips than the cell asks for, it exits non-zero and prints no result.
See ``chipbench/harness/cell.py`` for what a run does.
"""
import os
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
sys.path.insert(0, HERE)

if __name__ == "__main__":
    try:
        from harness.cell import main
        rc = main(t_start=T_START)
    except Exception:  # noqa: BLE001 - report, exit non-zero, no result
        import traceback
        traceback.print_exc()
        rc = 1
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)
