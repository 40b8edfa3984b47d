"""One run of a cell as ``run.py`` makes it, with the program's span
recorder (``repro.core.spans``) on from window start to the end of the
drain, reporting the metrics that read the spans.

    python chipbench/spanprobe.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1> [--spans 0|1]

The benchmark's runs leave the recorder off: ``harness/cell.py`` has no
``Ctx.spans`` and does not turn it on (PERF.md, Open questions). Until it
does, this script does both around ``harness.cell.run``, adds the
per-layer metrics below (and, in a traced run, the end-to-end ones) to
the cell's list, and adds a ``spans`` key to the result line: how the
per-query spans tile each query's time to first token, the device's idle
seconds by the kind of host span that labels them, and the offset
``harness.spanread.align`` finds between the two clocks. ``--spans 0``
makes the same run with the recorder off (its cost when on is the
difference).
"""
import copy
import os
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
sys.path.insert(0, HERE)

from harness import cell, serve, spanread, spec, tracing  # noqa: E402

# the entries BENCHMARK.json's per_layer list would take for these metrics
SPAN_METRICS = [
    {"name": "dispatch_ms_p90", "unit": "ms", "layer": "control plane"},
    {"name": "worker_queue_s_p50.chat", "unit": "s",
     "layer": "control plane"},
    {"name": "ttft_queue_share.chat", "unit": "%", "layer": "control plane"},
    {"name": "first_chunk_ms_p50.chat", "unit": "ms",
     "layer": "engine scheduler"},
    {"name": "stepper_host_ms_per_segment.chat", "unit": "ms",
     "layer": "runtime stepper"},
]


def entries(cell_name):
    return [dict(m, better="lower", source="program_span",
                 moves="tpot_p50_ms", workloads=[cell_name])
            for m in SPAN_METRICS]


def run(bench, name, seed, seconds, trace, t_start, spans_on=True, **kw):
    """``harness.cell.run`` with the recorder on (``spans_on``) and the
    span metrics added; returns the result line's object."""
    from repro.core import spans
    bench = copy.deepcopy(bench)
    bench["per_layer"] += entries(name)
    if trace:
        bench["per_layer"] += [dict(m) for m in bench["end_to_end"]
                               if m["name"] != "setup_s"]
    box = {}
    orig = (serve._Clock.run, cell.run_window, cell.Ctx, tracing.load)

    def clock_run(self):
        if spans_on:
            time.sleep(max(self.lo - time.perf_counter(), 0.0))
            spans.enable()
        orig[0](self)

    def run_window(*a, **k):
        try:
            got = orig[1](*a, **k)
            box["win"] = got[1]
            return got
        finally:
            spans.disable()
            box["spans"] = spans.drain() if spans_on else None

    def load(trace_dir):
        box["trace"] = orig[3](trace_dir)
        return box["trace"]

    class SpanCtx(cell.Ctx):
        @property
        def spans(self):
            return box.get("spans")

    def start_trace(*a, **k):
        start(*a, **k)
        spans.anchor()

    import jax
    start = jax.profiler.start_trace
    serve._Clock.run, cell.run_window, cell.Ctx, tracing.load = \
        clock_run, run_window, SpanCtx, load
    jax.profiler.start_trace = start_trace
    try:
        out = cell.run(bench, name, seed, seconds, trace, t_start, **kw)
    finally:
        serve._Clock.run, cell.run_window, cell.Ctx, tracing.load = orig
        jax.profiler.start_trace = start
    if box.get("spans"):
        out["spans"] = summary(box["spans"], box["win"], box.get("trace"))
        checks = out.pop("checks")
        out["checks"] = checks            # stays the last key
    return out


def summary(recs, win, tr):
    """How the per-query spans tile the time to first token of each query
    dispatched in the window, the device's idle seconds by the kind of
    host span labelling them, and the offset between the recorder's clock
    and the trace's."""
    tiles = [spanread.tiling(d) for d in spanread.by_query(recs).values()
             if win.lo <= min(r.t0_ns for r in d["master.dispatch"]) * 1e-9
             < win.hi]
    res = [abs(x) for t in tiles if t is not None for x in t]
    out = {"records": len(recs), "queries": len(tiles),
           "tiled": sum(t is not None for t in tiles),
           "tiling_residual_ms_max": max(res, default=0) * 1e-6}
    if tr is None:
        return out
    names = {r.name for r in recs}
    if tr.devices:
        by = tracing.gaps_by_label(tr.devices[0], tr.host, tr.lo, tr.hi,
                                   n=10 ** 6)
        kind = {"program span": 0.0, "host idle": 0.0, "other": 0.0}
        for label, s in by:
            kind["program span" if label in names else
                 "host idle" if label == "host idle" else "other"] += s
        out["idle_s_by_kind"] = kind
        out["idle_top"] = by[:15]
        out["idle_s_innermost"] = spanread.idle_by_span(
            tr.devices[0], tr.host, tr.lo, tr.hi, names)[:15]
    off = spanread.align(tr, recs)
    if off is not None:
        out["align_offset_s"] = off
        out["align_matched"] = spanread.matched(tr, recs, off)
        out["trace_program_spans"] = sum(1 for n, _, _ in tr.host
                                         if n in names)
    return out


def main(argv=None):
    import argparse
    import json
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--spans", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    try:
        out = run(spec.load_benchmark(), args.workload, args.seed,
                  args.seconds, bool(args.trace), T_START,
                  spans_on=bool(args.spans))
    except cell.NoChip as e:
        cell.log(f"[chipbench] {e}")
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    try:
        rc = main()
    except Exception:  # noqa: BLE001 - report, exit non-zero, no result
        import traceback
        traceback.print_exc()
        rc = 1
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)
