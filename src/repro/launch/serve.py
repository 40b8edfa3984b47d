"""Cluster serving launcher.

Brings up the INFaaS control plane (master + workers + autoscalers),
registers the selected architectures, and drives a Poisson workload.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b \
        --workers 2 --rate 50 --duration 60 --slo-ms 100

``--backend`` picks the data plane behind the workers:

* ``sim`` (default) — profile-driven executors; any scale, no JAX
  execution.
* ``real`` — every worker runs an ``EngineExecutor``: jobs execute for
  real on continuous-batching engines, measured service times drive the
  clock, and variant profiles are re-fit from the measurements (reported
  at the end).

``--size`` sets the model the real data plane builds: ``reduced`` (the
default; the family's tiny CPU smoke config) or ``published`` (the
architecture's published widths, for an accelerator).

``--real-engine`` instead drives one real continuous-batching engine
directly (no control plane) with a mixed-length stream and reports
measured tokens/sec and compile counts — the standalone data-plane check.

``--clock wall`` (requires ``--backend real``) runs the control plane
against ``RealClock`` as a long-running server: a seeded Poisson client
submits payload-carrying queries live, stepper threads drive the engines,
and tokens stream back per decode segment (TTFT is reported alongside
completion latency). SIGINT drains in-flight work and exits cleanly.
"""
from __future__ import annotations

import argparse
from typing import Optional, Sequence

from repro.configs.base import ArchConfig
from repro.configs.registry import ARCHS
from repro.core.api import QueryPayload, QuerySpec
from repro.sim.cluster import make_cluster
from repro.sim.workload import poisson_arrivals
from benchmarks.common import steady_metrics  # noqa: E402


def _sized(arch: str, size: str) -> ArchConfig:
    """The config the real data plane builds for ``arch`` at ``size``."""
    cfg = ARCHS[arch]
    return cfg.reduced() if size == "reduced" else cfg


def _real_engine_demo(arch: str, size: str, n_reqs: int, slots: int,
                      page_size: Optional[int] = None,
                      n_pages: Optional[int] = None,
                      chunk_threshold: Optional[int] = None,
                      stage_slots: int = 0,
                      admission: str = "worstcase",
                      preempt_policy: str = "slack",
                      prefix_cache: bool = False,
                      prefix_evict: str = "lru",
                      quantize: str = "none",
                      speculate: Optional[str] = None) -> None:
    import dataclasses
    import time

    import jax
    import numpy as np

    from repro.models import build_model
    from repro.serving.engine import Request, ServingEngine

    base_cfg = _sized(arch, size)
    fp_model = build_model(base_cfg)
    fp_params = fp_model.init(jax.random.PRNGKey(0))
    cfg, model, params = base_cfg, fp_model, fp_params
    if quantize == "int8":
        # weight-only quantized serving: int8 projection weights derived
        # from the fp init, dequantized inside the matmuls
        from repro.models.quantize import quantize_params_dense
        cfg = dataclasses.replace(base_cfg, quantize="int8")
        model = build_model(cfg)
        params = quantize_params_dense(fp_params)
    spec_kwargs = {}
    if speculate:
        dname, _, ks = speculate.partition(":")
        k = int(ks) if ks else 4
        if dname == "int8":
            # int8 variant of the same arch as the draft: same weights
            # modulo rounding, so its argmaxes track the target closely
            from repro.models.quantize import quantize_params_dense
            dm = build_model(dataclasses.replace(base_cfg, quantize="int8"))
            dp = quantize_params_dense(fp_params)
        else:
            dm = build_model(_sized(dname, size))
            dp = dm.init(jax.random.PRNGKey(1))
        spec_kwargs = dict(speculate=(dm, dp), spec_k=k)
    eng = ServingEngine(model, params, max_batch=slots, max_len=64,
                        decode_block=16, page_size=page_size,
                        n_pages=n_pages, chunk_threshold=chunk_threshold,
                        stage_slots=stage_slots, admission=admission,
                        preempt_policy=preempt_policy,
                        prefix_cache=prefix_cache,
                        prefix_evict=prefix_evict, **spec_kwargs)
    rng = np.random.default_rng(0)
    # with the prefix cache on, give the stream something to share: half
    # the requests open with a common template (a system prompt stand-in)
    tpl = (rng.integers(0, cfg.vocab, size=24).astype(np.int32)
           if prefix_cache else None)

    def _prompt(i):
        body = rng.integers(0, cfg.vocab,
                            size=int(rng.integers(4, 29))).astype(np.int32)
        if tpl is not None and i % 2 == 0:
            # stay inside max_len 64 with max_new up to 32
            return np.concatenate([tpl, body])[:32]
        return body

    reqs = [Request(rid=i, prompt=_prompt(i),
                    max_new_tokens=int(rng.integers(4, 33)))
            for i in range(n_reqs)]
    eng.warmup(prompt_lens=[len(r.prompt) for r in reqs])
    t0 = time.perf_counter()
    eng.serve(reqs)
    wall = time.perf_counter() - t0
    toks = sum(len(r.tokens) for r in reqs)
    s = eng.stats
    layout = (f"paged {eng.n_pages}x{eng.page_size}"
              if eng._paged else "contiguous")
    print(f"real engine [{cfg.name}] ({layout}): "
          f"{len(reqs)} reqs / {toks} tokens in "
          f"{wall*1e3:.1f} ms = {toks/wall:.0f} tok/s "
          f"({s['prefill_dispatches']}+{s['decode_dispatches']} dispatches, "
          f"{s['prefill_traces']}+{s['decode_traces']} compiles, "
          f"peak {s['peak_concurrency']} slots, "
          f"{s['chunk_admits']} chunked admits, "
          f"{s['inseg_admissions']} in-segment admits, "
          f"{s['preemptions']} preemptions, "
          f"segment occupancy {eng.occupancy['slot_busy_frac']:.2f})")
    if eng._prefix is not None:
        print(f"  prefix cache: {s['prefix_hits']} hits, "
              f"{s['prefix_pages_reused']} pages reused, "
              f"{s['prefix_tokens_skipped']} prefill tokens skipped, "
              f"{s['cow_copies']} COW copies, "
              f"{s['evictions']} evictions")
    if eng._spec is not None:
        print(f"  speculative decode (k={eng.spec_k}): "
              f"{s['spec_rounds']} rounds, "
              f"{s['spec_accepted']}/{s['spec_proposed']} draft tokens "
              f"accepted "
              f"(rate {eng.occupancy['spec_accept_rate']:.2f})")


def _serve_wall(c, archs: Sequence[ArchConfig], args) -> None:
    """Long-running wall-clock server: a seeded Poisson client submits
    payload-carrying queries on the RealClock scheduler thread, tokens
    stream back per decode segment, and SIGINT (or the duration horizon)
    drains in-flight work before a clean exit."""
    import signal
    import threading
    import time

    import numpy as np

    from benchmarks.common import pct

    rng = np.random.default_rng(0)
    # payload shape fits the default reduced engine (max_len 32): several
    # decode segments per request so TTFT genuinely precedes completion
    prompt_lens = (4, 13)
    max_new = 8
    arch_names = [a.name for a in archs]
    vocabs = {a.name: a.vocab for a in archs}
    handles: list = []
    streamed = {"chunks": 0, "tokens": 0}
    stop = threading.Event()
    loop = c.loop

    def on_sigint(signum, frame):
        print("\nSIGINT: draining in-flight work...", flush=True)
        stop.set()

    prev = signal.signal(signal.SIGINT, on_sigint)

    def count(chunk):
        streamed["chunks"] += 1
        streamed["tokens"] += len(chunk.tokens)

    def fire():
        # runs on the scheduler thread — the master's dispatch is just
        # another clock callback, so no cross-thread marshaling needed
        if stop.is_set():
            return
        a = arch_names[int(rng.integers(len(arch_names)))]
        prompt = rng.integers(
            0, vocabs[a],
            size=int(rng.integers(*prompt_lens))).astype(np.int32)
        h = c.api.submit(QuerySpec.arch(
            a, latency_ms=args.slo_ms,
            payload=QueryPayload.of([prompt], max_new_tokens=max_new)))
        h.on_tokens(count)
        handles.append(h)

    # seeded Poisson arrivals over [0, duration), scheduled up front on
    # the wall clock (the scheduler thread fires them as time passes)
    t0 = loop.now()
    t = float(rng.exponential(1.0 / max(args.rate, 1e-9)))
    n_arrivals = 0
    while t < args.duration:
        loop.schedule_at(t0 + t, fire)
        n_arrivals += 1
        t += float(rng.exponential(1.0 / max(args.rate, 1e-9)))

    while not stop.is_set():
        if loop.now() - t0 >= args.duration and \
                all(h.done for h in list(handles)):
            break
        time.sleep(0.05)

    # drain: queries already in the system stream out; SIGINT only stops
    # new arrivals (fire checks the flag)
    deadline = time.monotonic() + 30.0
    while not all(h.done for h in list(handles)):
        if time.monotonic() >= deadline:
            print("drain timeout: abandoning remaining work", flush=True)
            break
        time.sleep(0.05)
    for ex in getattr(c, "executors", []):
        ex.shutdown()
    loop.shutdown()
    signal.signal(signal.SIGINT, prev)

    done = [h for h in handles if h.done]
    results = [h.result(timeout=0.001) for h in done]
    ok = [r for r in results if r.ok]
    ttfts = [h.ttft for h in done if h.ttft is not None]
    lats = [r.latency for r in ok]
    wall = loop.now() - t0
    print(f"wall-clock serve [{'/'.join(arch_names)}]: "
          f"{n_arrivals} arrivals, {len(handles)} submitted, "
          f"{len(ok)} completed ok, "
          f"{sum(1 for r in results if r.failed)} failed "
          f"in {wall:.1f}s wall ({len(ok)/max(wall, 1e-9):.2f} q/s)")
    print(f"streamed: {streamed['tokens']} tokens in "
          f"{streamed['chunks']} chunks across {len(done)} queries")
    if ttfts and lats:
        print(f"TTFT p50={pct(ttfts, 50)*1e3:.0f}ms "
              f"p99={pct(ttfts, 99)*1e3:.0f}ms | completion "
              f"p50={pct(lats, 50)*1e3:.0f}ms "
              f"p99={pct(lats, 99)*1e3:.0f}ms")
    print("clean shutdown: drained in-flight work", flush=True)


def main(argv: Optional[Sequence[str]] = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b",
                    help="architecture id, or 'all'")
    ap.add_argument("--backend", choices=["sim", "real"], default="sim",
                    help="worker data plane: profiled t(b) models (sim) or "
                         "real reduced-config engines (real)")
    ap.add_argument("--clock", choices=["virtual", "wall"],
                    default="virtual",
                    help="virtual: discrete-event simulation of time; "
                         "wall: long-running server on RealClock with "
                         "threaded engine stepping and token streaming "
                         "(needs --backend real)")
    ap.add_argument("--size", choices=["reduced", "published"],
                    default="reduced",
                    help="model the real data plane builds: the tiny CPU "
                         "smoke config (reduced) or the published widths "
                         "(published); the sim backend always plans with "
                         "published profiles")
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--cpu-workers", type=int, default=1)
    ap.add_argument("--rate", type=float, default=50.0, help="queries/s")
    ap.add_argument("--duration", type=float, default=60.0)
    ap.add_argument("--slo-ms", type=float, default=100.0)
    ap.add_argument("--no-autoscale", action="store_true")
    ap.add_argument("--hedge", action="store_true",
                    help="enable hedged-request straggler mitigation")
    ap.add_argument("--real-engine", action="store_true",
                    help="drive one real continuous-batching engine "
                         "directly, without the control plane")
    ap.add_argument("--real-reqs", type=int, default=32)
    ap.add_argument("--real-slots", type=int, default=8)
    ap.add_argument("--page-size", type=int, default=None,
                    help="paged KV cache page size in positions "
                         "(default: contiguous max-shape slots)")
    ap.add_argument("--n-pages", type=int, default=None,
                    help="KV page pool size (default: max_batch * "
                         "max_len / page_size, capacity parity)")
    ap.add_argument("--chunk-threshold", type=int, default=None,
                    help="chunk prompts longer than this through the "
                         "decode loop instead of one prefill dispatch")
    ap.add_argument("--stage-slots", type=int, default=0,
                    help="in-segment admission: device staging ring "
                         "capacity (0 = boundary-only admission)")
    ap.add_argument("--admission", choices=["worstcase", "optimistic"],
                    default="worstcase",
                    help="paged admission control: reserve worst-case "
                         "pages up front, or admit on expected usage and "
                         "preempt under pressure (needs --page-size)")
    ap.add_argument("--preempt-policy", choices=["slack", "lru"],
                    default="slack",
                    help="optimistic-admission victim choice: most SLO "
                         "slack, or most-recently-admitted (lru)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share common prompt prefixes at page "
                         "granularity across requests (copy-on-write; "
                         "needs --page-size)")
    ap.add_argument("--prefix-evict", choices=["lru", "fifo"],
                    default="lru",
                    help="which unreferenced cached page the pool "
                         "reclaims first when it runs dry")
    ap.add_argument("--quantize", choices=["none", "int8"], default="none",
                    help="serve weight-only int8-quantized projection "
                         "weights (dense-family archs; real data plane "
                         "only)")
    ap.add_argument("--swap", choices=["host"], default=None,
                    help="preemption recovery: gather the victim's live "
                         "KV pages to a host-RAM buffer and scatter them "
                         "back at re-admission (zero replay compute) "
                         "instead of recomputing; real backend only")
    ap.add_argument("--swap-budget-mb", type=int, default=None,
                    help="cap on live host-swapped KV bytes; past it "
                         "preemptions fall back to recompute (needs "
                         "--swap host)")
    ap.add_argument("--breaker", action="store_true",
                    help="health-aware routing: per-worker circuit "
                         "breaker fed by dispatch failures, engine "
                         "pressure signals, and heartbeat staleness; "
                         "real backend only")
    ap.add_argument("--deadline-enforce", action="store_true",
                    help="cancel queries past their SLO deadline at the "
                         "engine (frees their pages immediately; the "
                         "result carries a timed-out verdict); real "
                         "backend only")
    ap.add_argument("--speculate", default=None, metavar="DRAFT[:K]",
                    help="speculative decoding: draft variant DRAFT "
                         "('int8' = the int8 variant of the served arch, "
                         "or another arch id) proposes K tokens per round "
                         "(default 4); real data plane only")
    args = ap.parse_args(argv)

    if args.speculate is not None:
        dname, _, ks = args.speculate.partition(":")
        if not dname or (ks and not ks.isdigit()):
            raise SystemExit("--speculate takes DRAFT[:K], e.g. 'int8:4' "
                             "or 'llama3.2-1b:7'")
    if args.backend != "real" and not args.real_engine and \
            (args.speculate is not None or args.quantize != "none"):
        raise SystemExit(
            "--speculate/--quantize reshape the real decode loop; combine "
            "them with --backend real or --real-engine (the sim backend "
            "has no decode loop to speculate in and no weights to "
            "quantize)")

    if args.swap_budget_mb is not None and args.swap is None:
        raise SystemExit("--swap-budget-mb bounds the host swap buffer; "
                         "it needs --swap host")
    if (args.swap is not None or args.breaker or args.deadline_enforce) \
            and (args.backend != "real" or args.real_engine):
        raise SystemExit(
            "--swap/--breaker/--deadline-enforce wire the control plane "
            "to the real data plane (host KV swap, engine pressure "
            "signals, engine-level cancellation); combine them with "
            "--backend real (the sim backend has no KV bytes to swap and "
            "no decode loop to cancel, and --real-engine bypasses the "
            "control plane)")
    if args.deadline_enforce and args.clock != "wall":
        raise SystemExit(
            "--deadline-enforce cancels overdue engine slots from the "
            "threaded stepper, which only exists under --clock wall; on "
            "the virtual clock it would be silently inert")
    if args.n_pages is not None and args.page_size is None:
        raise SystemExit("--n-pages sizes the paged KV pool; it needs "
                         "--page-size (contiguous engines have no pool)")
    if args.admission == "optimistic" and args.page_size is None:
        raise SystemExit("--admission optimistic over-commits the paged "
                         "KV pool; it needs --page-size (contiguous "
                         "engines reserve whole slots and cannot "
                         "over-commit)")
    if args.prefix_cache and args.page_size is None:
        raise SystemExit("--prefix-cache shares prompt prefixes at page "
                         "granularity; it needs --page-size (contiguous "
                         "slot rows have no pages to share)")
    if args.clock == "wall" and (args.backend != "real"
                                 or args.real_engine):
        raise SystemExit("--clock wall runs the control plane in real "
                         "time against live engines; it needs --backend "
                         "real (the sim executor resolves service times "
                         "instantly and has nothing to do on a wall "
                         "clock, and --real-engine bypasses the control "
                         "plane entirely)")
    if args.real_engine:
        _real_engine_demo(args.arch, args.size, args.real_reqs,
                          args.real_slots,
                          page_size=args.page_size, n_pages=args.n_pages,
                          chunk_threshold=args.chunk_threshold,
                          stage_slots=args.stage_slots,
                          admission=args.admission,
                          preempt_policy=args.preempt_policy,
                          prefix_cache=args.prefix_cache,
                          prefix_evict=args.prefix_evict,
                          quantize=args.quantize,
                          speculate=args.speculate)
        return

    if args.backend != "real" and (args.page_size is not None
                                   or args.n_pages is not None
                                   or args.chunk_threshold is not None
                                   or args.stage_slots
                                   or args.admission != "worstcase"
                                   or args.prefix_cache):
        raise SystemExit(
            "--page-size/--n-pages/--chunk-threshold/--stage-slots/"
            "--admission/--prefix-cache configure the real data plane; "
            "combine them with --backend real or --real-engine (the sim "
            "backend has no KV cache to page and no decode loop to "
            "refill)")
    if args.backend == "real" and args.arch == "all":
        raise SystemExit("--backend real needs a single --arch "
                         "(each arch builds real model params)")
    if args.arch == "all":
        archs = None
    elif args.backend == "real":
        archs = [_sized(args.arch, args.size)]
    else:
        archs = [ARCHS[args.arch]]
    from repro.core.master import MasterConfig
    cfg = MasterConfig(hedge_enabled=args.hedge,
                       breaker_enabled=args.breaker)
    engine_cfg = None
    if args.backend == "real" and (args.page_size is not None
                                   or args.n_pages is not None
                                   or args.chunk_threshold is not None
                                   or args.stage_slots
                                   or args.admission != "worstcase"
                                   or args.prefix_cache
                                   or args.quantize != "none"
                                   or args.speculate is not None
                                   or args.swap is not None
                                   or args.deadline_enforce):
        from repro.serving.executor import EngineExecutorConfig
        engine_cfg = EngineExecutorConfig(
            page_size=args.page_size, n_pages=args.n_pages,
            chunk_threshold=args.chunk_threshold,
            stage_slots=args.stage_slots,
            admission=args.admission,
            preempt_policy=args.preempt_policy,
            prefix_cache=args.prefix_cache,
            prefix_evict=args.prefix_evict,
            quantize=args.quantize,
            speculate=args.speculate,
            swap=args.swap,
            swap_budget_bytes=None if args.swap_budget_mb is None
            else args.swap_budget_mb * 2**20,
            deadline_enforce=args.deadline_enforce)
    c = make_cluster(n_accel=args.workers, n_cpu=args.cpu_workers,
                     archs=archs, autoscale=not args.no_autoscale, cfg=cfg,
                     backend=args.backend, engine_cfg=engine_cfg,
                     clock=args.clock)
    if args.clock == "wall":
        _serve_wall(c, archs, args)
        return
    arch_names = [a.name for a in archs] if archs else list(ARCHS)

    import numpy as np
    rng = np.random.default_rng(0)

    def fire(t):
        a = arch_names[rng.integers(len(arch_names))]
        c.api.submit(QuerySpec.arch(a, latency_ms=args.slo_ms))

    poisson_arrivals(c.loop, lambda t: args.rate, fire,
                     t_end=args.duration, seed=0)
    c.run_until(args.duration + 30.0)
    m = steady_metrics(c.master.metrics, 0.0, args.duration + 30.0,
                       warmup=min(20.0, args.duration / 3.0))
    print(f"served={m['completed']} thr={m['throughput_qps']:.1f} q/s "
          f"viol={m['violation_rate']:.3f} p50={m['p50_ms']:.1f}ms "
          f"p99={m['p99_ms']:.1f}ms")
    alive = sum(1 for w in c.store.workers.values() if w.alive)
    print(f"workers alive at end: {alive}")
    if args.backend == "real":
        measured = [v for v in c.store.registry.variants.values()
                    if v.profile.source == "measured"]
        for v in measured:
            print(f"measured profile {v.name}: "
                  f"t(b) = {v.profile.m*1e3:.2f}ms*b + "
                  f"{v.profile.c*1e3:.2f}ms")
        print(f"variants re-fit from real measurements: {len(measured)}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
