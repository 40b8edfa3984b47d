"""Find the highest open-loop rate a cell's configuration sustains.

    python chipbench/sweep.py --workload <cell> --seeds 1,2,3 --seconds 30 \
        --rates 1,2,3 [--deploy slots=8 --deploy variant=<name>]

One process: set up the cell's server once (weights from the first seed),
warm every bucket, then for each rate (ascending) and each seed run the
cell's traffic mix at that rate for ``--seconds`` and drain. ``--deploy``
replaces a key of the configuration's deployment, to try one before it is
written into the configuration's file.

A rate is sustained when no request due in the window failed on any seed,
and the number of requests in flight (sent, not yet finished) did not
grow across the window: its mean over the window's last quarter exceeds
the mean over its first quarter by at most one request, averaged over the
seeds. The sweep stops after two rates in a row that are not sustained.
Prints one line per rate and seed, one per rate, the device's peak
memory, and last a JSON object with every reading. The cell's rate is
then written into its traffic file by hand, at about four fifths of the
highest sustained rate.
"""
import os
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
sys.path.insert(0, HERE)

GROWTH_LIMIT = 1.0   # requests in flight, last quarter minus first


def one_rate(server, tr, seconds, drain_cap):
    import threading

    from harness import stats
    from harness.serve import run_window

    samples, made = [], []
    stop = threading.Event()

    def sampler():
        while not stop.is_set():
            samples.append((time.perf_counter(),
                            sum(1 for x in list(made) if x.done < 0)))
            time.sleep(0.25)
    th = threading.Thread(target=sampler, daemon=True)
    th.start()
    recs, win = run_window(server, tr, seconds, recs=made)
    stop.set()
    th.join()
    inw = [x for x in recs if win.lo <= x.due < win.hi]
    q = [n for t, n in samples if win.lo <= t < win.hi]
    k = max(len(q) // 4, 1)
    ttft = [(x.first - x.due) if x.ok and x.first else None for x in inw]
    return {"due": len(inw), "unfinished": sum(1 for x in inw if not x.ok),
            "inflight_first_q": sum(q[:k]) / k,
            "inflight_last_q": sum(q[-k:]) / k,
            "ttft_p50": stats.pct(ttft, 50, drain_cap),
            "ttft_p90": stats.pct(ttft, 90, drain_cap)}


def main() -> int:
    import argparse
    import copy
    import json

    import jax

    from harness import spec
    from harness import weights as W
    from harness.cell import bucket_of, enable_cache
    from harness.serve import Server, warmup
    from harness.traffic import generate

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--deploy", action="append", default=[],
                    help="key=value (JSON value) replacing a deployment key")
    a = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    enable_cache()
    bench = spec.load_benchmark()
    wl = spec.workload(bench, a.workload)
    cfg = copy.deepcopy(spec.config(bench, wl["config"]))
    dep = cfg["deployment"]
    for kv in a.deploy:
        k, v = kv.split("=", 1)
        try:
            dep[k] = json.loads(v)
        except ValueError:
            dep[k] = v
    mix0 = spec.traffic(wl["traffic"])
    dm = W.dims(cfg)
    rates = [float(x) for x in a.rates.split(",")]
    seeds = [int(x) for x in a.seeds.split(",")]
    plan = {}
    for r in rates:
        m = copy.deepcopy(mix0)
        m["arrivals"]["rate_per_s"] = r
        for s in seeds:
            plan[r, s] = generate(m, dm.vocab, s, a.seconds)
    server = Server(cfg, W.make(dm, seeds[0]))
    allp = copy.copy(next(iter(plan.values())))
    allp.prompts = [p for t in plan.values() for p in t.prompts]
    warmup(server, allp, dep["slots"], bucket_of(dep),
           dep["decode_block"] + 1)
    print(f"deployment {json.dumps(dep)}", flush=True)
    print(f"set-up {time.perf_counter() - T_START:.1f} s", flush=True)
    rows, misses = [], 0
    for r in rates:
        per_seed = []
        for s in seeds:
            row = dict(rate=r, seed=s, **one_rate(
                server, plan[r, s], a.seconds, mix0["drain_cap_s"]))
            per_seed.append(row)
            print(json.dumps(row), flush=True)
        growth = sum(x["inflight_last_q"] - x["inflight_first_q"]
                     for x in per_seed) / len(per_seed)
        ok = all(x["unfinished"] == 0 for x in per_seed) \
            and growth <= GROWTH_LIMIT
        summary = {"rate": r, "growth": growth, "sustained": ok,
                   "ttft_p90_by_seed": [x["ttft_p90"] for x in per_seed]}
        rows.append({"summary": summary, "runs": per_seed})
        print("RATE " + json.dumps(summary), flush=True)
        misses = 0 if ok else misses + 1
        if misses >= 2:
            break
    mem = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    print(f"peak_bytes_in_use {mem}", flush=True)
    server.shutdown()
    print(json.dumps({"deployment": dep, "peak_bytes_in_use": mem,
                      "rates": rows}), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    os._exit(rc)
