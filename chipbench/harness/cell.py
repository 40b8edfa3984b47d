"""One run of one cell: set up, measure, check, reduce to metrics.

Order of a run (``run``):

1. find the cell's configuration, traffic mix and settings by name;
2. make the weights on the device from the seed (one jitted call) and
   hand them to a wall-clock cluster serving the configuration's variant;
3. warm every prompt bucket the seeded traffic will hit;
4. ramp, measure ``seconds``, drain (``harness.serve.run_window``),
   optionally tracing the device;
5. read the device's peak memory, free the program's state;
6. run the plain reference over a seeded sample of the finished requests
   and compare (``harness.check``);
7. read every metric the cell reports (``chipbench/metrics/<name>.py``).

Set-up (``setup_s``) runs from process start to the first counted
request, so it holds steps 2 and 3 and the ramp. The reference runs after
the window and is not in it.
"""
from __future__ import annotations

import dataclasses
import gc
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

from harness import check, counts, spec, tracing
from harness import weights as W
from harness.peaks import peak
from harness.serve import CompileLog, Server, run_window, warmup
from harness.traffic import generate

# the gap reported when no request finished to be compared (JSON has no
# infinity); any limit fails it
NOTHING_COMPARED = 1e9
TRACE_COUNTERS = ("prefill_traces", "decode_traces", "chunk_traces",
                  "verify_traces")


class NoChip(Exception):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Ctx:
    """What a metric reader sees."""
    cell: str
    cfg: Dict[str, Any]
    dm: W.Dims
    peaks: Dict[str, float]
    seconds: float
    drain_cap: float
    setup_s: float
    records: List[Any]            # requests due in the window
    sent: List[Any]               # every request sent
    win: Any                      # harness.serve.Window
    trace: Optional[tracing.Trace]

    def in_window(self, t: float) -> bool:
        return self.win.lo <= t < self.win.hi

    def in_trace(self, t: float) -> bool:
        return self.win.trace_lo <= t < self.win.trace_hi

    def delta(self, key: str, traced: bool = False) -> int:
        a, b = ((self.win.stats_tlo, self.win.stats_thi) if traced
                else (self.win.stats_lo, self.win.stats_hi))
        return b.get(key, 0) - a.get(key, 0)

    def work(self, inside) -> Dict[str, float]:
        """Needed work of what was delivered while ``inside(t)`` holds:
        prefill of every request whose first chunk arrived then, decode
        of every later token that arrived then (by chunk time)."""
        dm = self.dm
        out = {"prefill_flops": 0, "prefill_kv_bytes": 0, "prefills": 0,
               "decode_flops": 0, "decode_kv_bytes": 0, "tokens": 0}
        for r in self.sent:
            L = len(r.prompt)
            j = 0
            for k, (t, n, _toks) in enumerate(r.chunks):
                if inside(t):
                    if k == 0:
                        out["prefill_flops"] += counts.prefill_flops(dm, L)
                        out["prefill_kv_bytes"] += \
                            L * counts.kv_bytes_per_token(dm)
                        out["prefills"] += 1
                    f, kv = counts.request_decode(dm, L, range(j, j + n))
                    out["decode_flops"] += f
                    out["decode_kv_bytes"] += kv
                    out["tokens"] += n
                j += n
        return out


def bucket_of(dep: Dict[str, Any]):
    def bucket(n: int) -> int:
        b = max(dep["min_bucket"], 1 << max(int(n) - 1, 0).bit_length())
        return min(b, dep["max_len"])
    return bucket


def enable_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (whatever the environment says), so that only the first run
    of a cell in a checkout compiles and two checkouts share nothing."""
    import jax
    from repro.launch.compile_cache import DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    # keep every program, however fast it compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(DEFAULT_DIR)


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def run(bench: Dict[str, Any], name: str, seed: int, seconds: float,
        trace: bool, t_start: float, *, require_chip: bool = True,
        cfg: Optional[Dict[str, Any]] = None,
        mix: Optional[Dict[str, Any]] = None,
        cell: Optional[Dict[str, Any]] = None,
        peaks: Optional[Dict[str, float]] = None,
        trace_dump: Optional[str] = None, control: Optional[str] = None,
        keep: Optional[Dict[str, Any]] = None,
        cache: bool = True) -> Dict[str, Any]:
    """Run one cell and return the result line's object. ``cfg``, ``mix``,
    ``cell`` and ``peaks`` replace what the files say (tests drive a
    small size on the CPU with them, and ``require_chip=False``).
    ``control`` names a lower precision of the reference (the
    configuration's ``control``) to put in the program's place: the
    served tokens are then the ones it puts first at each position of the
    served sequences, and ``checks`` and ``correct`` judge those, with the
    program's own reading kept beside them as ``program_gap`` and
    ``program_correct`` (the benchmark's own runs never set it). ``keep``
    receives the sample and the gap readings. ``cache`` turns on JAX's
    persistent compilation cache in the checkout."""
    import jax
    wl = spec.workload(bench, name)
    cfg = cfg or spec.config(bench, wl["config"])
    mix = mix or spec.traffic(wl["traffic"])
    cell = cell or spec.cell(name)
    devs = jax.devices()
    dev = devs[0]
    if require_chip and (dev.platform != "tpu" or len(devs) < wl["chips"]):
        raise NoChip(f"cell {name} needs {wl['chips']} TPU chip(s); JAX "
                     f"found {len(devs)} {dev.platform} device(s) "
                     f"({dev.device_kind})")
    peaks = peaks or peak(dev.device_kind)
    log(f"[chipbench] {name}: {dev.platform} {dev.device_kind} "
        f"x{len(devs)}; compile cache "
        f"{enable_cache() if cache else 'off'}")
    compiles = CompileLog()
    dm = W.dims(cfg)
    dep = cfg["deployment"]
    traffic = generate(mix, dm.vocab, seed, seconds)

    params = W.make(dm, seed)
    jax.block_until_ready(params)
    server = Server(cfg, params)
    del params
    buckets = warmup(server, traffic, dep["slots"], bucket_of(dep),
                     dep["decode_block"] + 1)
    log(f"[chipbench] warmed buckets {buckets} in "
        f"{time.perf_counter() - t_start:.1f} s since start")

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    try:
        sent, win = run_window(server, traffic, seconds, trace_dir,
                               float(cell.get("trace_s", seconds)))
        end_stats = server.stats()
        setup_s = win.lo - t_start
        in_window = [r for r in sent if win.lo <= r.due < win.hi]
        new_programs = sum(end_stats.get(k, 0) - win.stats_lo.get(k, 0)
                           for k in TRACE_COUNTERS)
        compiled = compiles.between(win.lo, win.end)
        mem = (dev.memory_stats() or {}).get("peak_bytes_in_use")
        clean = server.shutdown(timeout=60.0)
        if not clean:
            log("[chipbench] warning: server did not drain within 60 s")
        server.ex.engines.clear()
        server.ex._models.clear()
        del server
        gc.collect()

        tr = None
        if trace_dir:
            tr = tracing.load(trace_dir)
            if trace_dump:
                for line in tracing.describe(trace_dir):
                    log("[trace] " + line[:400])
                tr.clip(tr.lo, min(tr.hi, tr.lo + 0.25)).save(trace_dump)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    # -- correctness: the plain reference over a seeded sample ----------
    missing = sum(1 for r in in_window if not r.ok)
    pick = check.draw(in_window, int(cell["sample"]), seed)
    samples = [check.Sample(in_window[i].prompt, in_window[i].served)
               for i in pick]
    t_ref = time.perf_counter()
    ref = spec.reference(cfg)
    g = check.gaps(ref, dm, seed, samples, control=control,
                   max_len=dep["max_len"]) \
        if samples else {"max_gap": NOTHING_COMPARED, "tokens": 0, "gaps": []}
    if keep is not None:
        keep.update(samples=samples, gaps=g)
    ref_s = time.perf_counter() - t_ref
    checks = {
        "logit_gap": (g["max_gap"], float(cell["logit_gap_limit"])),
        "unfinished": (missing, 0),
        "stream_mismatch": (sum(1 for r in sent if r.done >= 0
                                and not r.stream_ok), 0),
        "compiles_in_window": (len(compiled) + new_programs, 0),
    }
    correct = bool(samples) and all(v <= lim for v, lim in checks.values())
    program = None
    if control is not None:
        program = (g["max_gap"], correct)
        checks["logit_gap"] = (g.get("control_max_gap", NOTHING_COMPARED),
                               checks["logit_gap"][1])
        correct = bool(samples) and all(v <= lim
                                        for v, lim in checks.values())

    # -- metrics ---------------------------------------------------------
    ctx = Ctx(cell=name, cfg=cfg, dm=dm, peaks=peaks, seconds=seconds,
              drain_cap=traffic.drain_cap_s, setup_s=setup_s,
              records=in_window, sent=sent, win=win, trace=tr)
    kind = "per_layer" if trace else "end_to_end"
    metrics: Dict[str, Any] = {}
    for m in spec.metrics_of(bench, name, kind):
        v = spec.reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    late = sorted(win.late_s) or [0.0]
    log(f"[chipbench] requests in window {len(in_window)} (sent "
        f"{len(sent)}), unfinished {missing}; generator late p99 "
        f"{late[int(0.99 * (len(late) - 1))] * 1e3:.2f} ms; reference "
        f"{ref_s:.1f} s over {len(samples)} requests, {g['tokens']} "
        f"tokens; per-request gaps {[round(x, 4) for x in g['gaps']]}")
    for k, v in metrics.items():
        log(f"[metric] {k} = {v['value']} {v['unit']}")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": wl["chips"], "memory_peak_bytes": mem}
    out: Dict[str, Any] = {"correct": correct, "attempted": len(in_window),
                           "failed": missing, "metrics": metrics,
                           "device": device}
    if tr is not None:
        d0 = tr.devices[0] if tr.devices else None
        busy = [tracing.busy_s(d) for d in tr.devices[:wl["chips"]]]
        device["busy_s"] = sum(busy) / len(busy) if busy else 0.0
        device["window_s"] = win.trace_hi - win.trace_lo
        if d0 is not None:
            out["breakdown"] = {
                "device_ops": [[n, s] for n, s in tracing.top_ops(d0, 10)],
                "idle_gaps": [[n, s] for n, s in tracing.gaps_by_label(
                    d0, tr.host, tr.lo, tr.hi, 10)]}
    if program is not None:
        out["program_gap"], out["program_correct"] = program
        log(f"[chipbench] control {control} in the program's place: gaps "
            f"{[round(x, 4) for x in g.get('control_gaps', [])]}")
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        log(f"check {k} {v} limit {lim}")
    return out


def main(argv: Optional[List[str]] = None, t_start: float = 0.0) -> int:
    import argparse
    import json
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dump", default=None,
                    help="write the first 0.25 s of the reduced trace "
                         "(gzipped JSON) here")
    args = ap.parse_args(argv)
    try:
        bench = spec.load_benchmark()
        out = run(bench, args.workload, args.seed, args.seconds,
                  bool(args.trace), t_start or time.perf_counter(),
                  trace_dump=args.trace_dump)
    except NoChip as e:
        log(f"[chipbench] {e}")
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    os._exit(main())
