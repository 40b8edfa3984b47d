"""Drive the wall-clock server the way its users do, and record what each
request saw on the client's side.

The system under test is entered through its public surface only:
``make_cluster(..., backend="real", clock="wall")``, ``ServingRuntime``
and ``QuerySpec.variant(..., payload=...)``; streamed chunks arrive through
``QueryHandle.on_tokens`` and completion through ``add_done_callback``.
The one exception is the weights: the program builds its own from a seed,
so the harness hands it the tree it made (``harness.weights``) through the
executor's model cache, as a deployment hands it a checkpoint; the plain
reference then makes the same tree again for itself.

Every time here is ``time.perf_counter()`` on the client's side. An open
loop's request is timed from the moment it was due, not from when the
sender got to it, so a stalled server delays every later request.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from harness import weights as W
from harness.traffic import Traffic


@dataclasses.dataclass
class Record:
    idx: int
    prompt: np.ndarray
    max_new: int
    due: float = 0.0                 # when it was due to be sent
    sent: float = -1.0               # when submit() returned
    submit_s: float = 0.0            # submit() round trip
    chunks: List[tuple] = dataclasses.field(default_factory=list)
    done: float = -1.0
    ok: bool = False
    served: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros((0,), np.int32))
    stream_ok: bool = True           # streamed chunks == final outputs
    error: str = ""

    @property
    def first(self) -> Optional[float]:
        return self.chunks[0][0] if self.chunks else None


COMPILE_EVENTS = ("backend_compile", "cache_retrieval")


class CompileLog:
    """Times of every compile (or persistent-cache load) JAX reports."""

    def __init__(self):
        import jax
        self.times: List[tuple] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if any(k in event for k in COMPILE_EVENTS):
            self.times.append((time.perf_counter(), event, duration))

    def between(self, lo: float, hi: float) -> List[tuple]:
        return [t for t in self.times if lo <= t[0] < hi]


def to_program_params(tree: Dict[str, Any]) -> Dict[str, Any]:
    """The harness's weight tree in the program's parameter layout."""
    from repro.models.quantize import QuantizedWeight

    def leaf(w):
        return QuantizedWeight(w["w_q"], w["scales"]) \
            if isinstance(w, dict) else w
    lay = tree["layers"]
    return {"embed": tree["embed"], "head": tree["head"],
            "ln_f": tree["ln_f"],
            "layers": {"attn": {n: leaf(lay[n]) for n in W.ATTN},
                       "mlp": {n: leaf(lay[n]) for n in W.MLP},
                       "ln1": lay["ln1"], "ln2": lay["ln2"]}}


def arch_config(cfg: Dict[str, Any]):
    """The program's ``ArchConfig`` for a configuration file."""
    from repro.configs.base import ArchConfig
    dep = cfg["deployment"]
    return ArchConfig(
        name=dep["arch"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        head_dim=cfg["head_dim"], rope_theta=float(cfg["rope_theta"]),
        dtype=cfg["precision"]["activations"],
        param_dtype=cfg["precision"]["activations"],
        attention_impl=dep["attention"], remat=False,
        source=cfg["source"])


class Server:
    """One wall-clock cluster serving one variant of one configuration."""

    def __init__(self, cfg: Dict[str, Any], params: Dict[str, Any]):
        import dataclasses as dc
        from repro.core.master import MasterConfig
        from repro.models.model import build_model
        from repro.serving.executor import EngineExecutorConfig
        from repro.serving.runtime import ServingRuntime
        from repro.sim.cluster import make_cluster

        dep = cfg["deployment"]
        self.arch = arch_config(cfg)
        self.variant = dep["variant"]
        ecfg = EngineExecutorConfig(
            max_batch=dep["slots"], max_len=dep["max_len"],
            decode_block=dep["decode_block"], min_bucket=dep["min_bucket"],
            prompt_len=dep["min_bucket"], max_new=dep["decode_block"])
        self.cluster = make_cluster(
            n_accel=1, n_cpu=0, archs=[self.arch], backend="real",
            autoscale=False, cfg=MasterConfig(worker_autoscale=False),
            engine_cfg=ecfg, clock="wall")
        (self.ex,) = self.cluster.executors
        quant = cfg["precision"]["projections"] == "int8"
        model = build_model(dc.replace(self.arch, quantize="int8")
                            if quant else self.arch)
        key = f"{self.arch.name}::int8" if quant else self.arch.name
        self.ex._models[key] = (model, to_program_params(params))
        self.rt = ServingRuntime(self.cluster)

    def spec(self, prompt: np.ndarray, max_new: int):
        from repro.core.api import QueryPayload, QuerySpec
        return QuerySpec.variant(
            self.variant,
            payload=QueryPayload.of([prompt], max_new_tokens=int(max_new)))

    def engine(self):
        return self.ex.engines.get(self.variant)

    def stats(self) -> Dict[str, int]:
        eng = self.engine()
        return dict(eng.stats) if eng is not None else {}

    def send(self, rec: Record, on_done: Optional[Callable] = None) -> None:
        """Submit one request; its chunks and completion land on ``rec``."""
        t = time.perf_counter()
        h = self.rt.submit(self.spec(rec.prompt, rec.max_new))
        rec.sent = time.perf_counter()
        rec.submit_s = rec.sent - t

        def on_chunk(ch, rec=rec):
            rec.chunks.append((time.perf_counter(), len(ch.tokens),
                               tuple(ch.tokens)))

        def finished(handle, rec=rec):
            t_done = time.perf_counter()
            r = handle.result(timeout=60.0)
            rec.error = r.error or (
                f"failed={r.failed} attempts={r.attempts} "
                f"variant={r.variant!r} worker={r.worker!r}"
                if not r.ok else "")
            if r.ok and r.outputs:
                rec.served = np.asarray(r.outputs[0], np.int32)
                streamed = [t for c in rec.chunks for t in c[2]]
                rec.stream_ok = streamed == rec.served.tolist()
                rec.ok = len(rec.served) == rec.max_new
            rec.done = t_done
            if on_done is not None:
                on_done(rec)
        h.on_tokens(on_chunk)
        h.add_done_callback(finished)

    def shutdown(self, timeout: float = 60.0) -> bool:
        return self.rt.shutdown(drain=True, timeout=timeout)


def _wait(recs: List[Record], timeout: float) -> bool:
    end = time.perf_counter() + timeout
    while time.perf_counter() < end:
        if all(r.done >= 0 for r in recs):
            return True
        time.sleep(0.005)
    return all(r.done >= 0 for r in recs)


def warmup(server: Server, traffic: Traffic, slots: int,
           bucket_of: Callable[[int], int], max_new: int) -> List[int]:
    """Compile and run every prompt bucket the traffic will hit: one
    request per bucket alone, then one group of ``slots`` per bucket,
    through the public API. Returns the buckets."""
    by_bucket: Dict[int, np.ndarray] = {}
    for p in traffic.prompts:
        b = bucket_of(len(p))
        if b not in by_bucket or len(p) > len(by_bucket[b]):
            by_bucket[b] = p
    buckets = sorted(by_bucket)
    for b in buckets:
        rec = Record(-1, by_bucket[b], max_new)
        server.send(rec)
        if not _wait([rec], 1200.0) or not rec.ok:
            raise RuntimeError(f"warm-up request (bucket {b}) failed: "
                               f"{rec.error or 'no result'}")
    for b in buckets:
        recs = [Record(-1, by_bucket[b], max_new) for _ in range(slots)]
        for r in recs:
            server.send(r)
        if not _wait(recs, 1200.0) or not all(r.ok for r in recs):
            raise RuntimeError(f"warm-up group (bucket {b}) failed")
    return buckets


@dataclasses.dataclass
class Window:
    t0: float              # traffic start (ramp begins)
    lo: float              # window start
    hi: float              # window end
    end: float             # last due request finished (or drain cap)
    stats_lo: Dict[str, int]
    stats_hi: Dict[str, int]
    stats_tlo: Dict[str, int] = dataclasses.field(default_factory=dict)
    stats_thi: Dict[str, int] = dataclasses.field(default_factory=dict)
    trace_lo: float = 0.0
    trace_hi: float = 0.0
    late_s: List[float] = dataclasses.field(default_factory=list)


class _Clock(threading.Thread):
    """Marks the window: samples the engine's counters at its start and
    end, and starts and stops the device trace around it."""

    def __init__(self, server: Server, lo: float, hi: float,
                 trace_dir: Optional[str], trace_s: float):
        super().__init__(name="bench-window", daemon=True)
        self.server, self.lo, self.hi = server, lo, hi
        self.trace_dir, self.trace_s = trace_dir, trace_s
        self.stats_lo: Dict[str, int] = {}
        self.stats_hi: Dict[str, int] = {}
        self.stats_tlo: Dict[str, int] = {}
        self.stats_thi: Dict[str, int] = {}
        self.trace_lo = self.trace_hi = 0.0
        self.error: Optional[BaseException] = None

    def run(self) -> None:
        try:
            import jax
            time.sleep(max(self.lo - time.perf_counter(), 0.0))
            self.stats_lo = self.server.stats()
            if self.trace_dir:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 2
                jax.profiler.start_trace(self.trace_dir,
                                         profiler_options=opts)
                self.stats_tlo = self.server.stats()
                self.trace_lo = time.perf_counter()
                stop = min(self.hi, self.trace_lo + self.trace_s)
                time.sleep(max(stop - time.perf_counter(), 0.0))
                self.stats_thi = self.server.stats()
                self.trace_hi = time.perf_counter()
                jax.profiler.stop_trace()
            time.sleep(max(self.hi - time.perf_counter(), 0.0))
            self.stats_hi = self.server.stats()
        except BaseException as e:  # noqa: BLE001 - reported by the caller
            self.error = e


def run_window(server: Server, traffic: Traffic, seconds: float,
               trace_dir: Optional[str] = None, trace_s: float = 1e9,
               recs: Optional[List[Record]] = None) -> tuple:
    """Ramp, measure ``seconds``, then keep the load coming until every
    request due in the window has finished or the drain cap has passed.
    Returns (records, Window); a record's ``due`` says whether it was in
    the window. Records are appended to ``recs`` as they are sent, when
    given (a caller may watch it from another thread)."""
    t0 = time.perf_counter() + 0.05
    lo = t0 + traffic.ramp_s
    hi = lo + seconds
    cap = hi + traffic.drain_cap_s
    clock = _Clock(server, lo, hi, trace_dir, trace_s)
    clock.start()
    recs = [] if recs is None else recs
    late: List[float] = []

    def window_done() -> bool:
        return all(r.done >= 0 for r in recs if lo <= r.due < hi)

    n = len(traffic.prompts)
    if traffic.kind == "poisson":
        for i in range(n):
            due = t0 + traffic.offsets[i]
            if due >= hi and (window_done() or due >= cap):
                break
            time.sleep(max(due - time.perf_counter(), 0.0))
            rec = Record(i, traffic.prompts[i], traffic.max_new[i], due=due)
            recs.append(rec)
            late.append(time.perf_counter() - due)
            server.send(rec)
    else:
        free: "queue.Queue[int]" = queue.Queue()
        for c in range(traffic.clients):
            free.put(c)
        i = 0
        while True:
            try:
                free.get(timeout=0.05)
            except queue.Empty:
                if time.perf_counter() >= hi and (window_done()
                                                  or time.perf_counter()
                                                  >= cap):
                    break
                continue
            now = time.perf_counter()
            if now >= hi and (window_done() or now >= cap):
                break
            j = i % n
            rec = Record(i, traffic.prompts[j], traffic.max_new[j], due=now)
            i += 1
            recs.append(rec)
            server.send(rec, on_done=lambda _r: free.put(0))
    # the window's requests are done (or the cap passed); let what is
    # still in flight finish before the state is torn down
    _wait([r for r in recs if lo <= r.due < hi],
          max(cap - time.perf_counter(), 0.0))
    end = time.perf_counter()
    clock.join()
    if clock.error is not None:
        raise clock.error
    win = Window(t0=t0, lo=lo, hi=hi, end=end, stats_lo=clock.stats_lo,
                 stats_hi=clock.stats_hi, stats_tlo=clock.stats_tlo,
                 stats_thi=clock.stats_thi, trace_lo=clock.trace_lo,
                 trace_hi=clock.trace_hi, late_s=late)
    return recs, win
