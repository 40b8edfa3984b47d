"""EngineExecutor: the real data plane behind an INFaaS worker device.

Implements the worker's ``Executor`` protocol (``repro.core.worker``) over
per-variant continuous-batching ``ServingEngine`` instances, so the whole
control plane — per-query variant selection, adaptive batching, the
monitoring daemon, and two-level autoscaling — drives *live* JAX engines
instead of the profile-driven simulation:

* ``run(variant, batch, requests)`` builds (lazily) an engine for the
  variant, pushes the batch through the open-loop
  ``submit()``/``step()``/``drain_completions()`` core, and returns the
  measured wall-clock service time. That measured time becomes the job's
  duration on the worker's (virtual) clock, so queueing, utilization, and
  autoscaling decisions all reflect real execution speed.

* each ``ExecRequest`` in ``requests`` is one co-batched query: when it
  carries real payload prompts, every prompt becomes one
  ``serving.engine.Request`` and the generated token ids are handed back
  through the request's ``on_outputs`` sink (one array per prompt, in
  submission order) — a payload-carrying ``QuerySpec`` is served on its
  *actual* inputs, not synthetic stand-ins. Requests without prompts fall
  back to the synthetic shape (``prompt_len``/``max_new`` below), which
  keeps compile caches to one prefill bucket for pure-accounting load.

* every measurement is recorded per batch size, and once two distinct
  batch sizes have been observed the variant's ``VariantProfile`` is
  re-fit in place (``repro.core.profiler.refit_profile``): t(b) = m*b + c
  moves from the analytic roofline guess to calibrated reality, and
  selection improves as measurements accumulate (ROADMAP item: wire
  measured t(b) back into the variant profiles).

Model weights are built once per architecture and shared across the
variants (and, via ``model_cache``, across the cluster's workers); each
variant still gets its own engine so slot state never crosses variants.
Engines are warmed up at creation, keeping XLA compile time out of the
measured service times. With ``max_engines`` set, the per-variant engine
map is an LRU: the least-recently-run variant's engine is dropped when the
cap is hit (engines are idle between ``run()`` calls, so nothing in flight
is lost) and rebuilds lazily — warmup happens at rebuild, outside the
measured window — keeping multi-arch ``backend="real"`` clusters
host-sized. ``page_size``/``n_pages``/``chunk_threshold``/``stage_slots``
pass through to the engines: the paged KV data plane, chunked prefill,
and in-segment admission under the full INFaaS control plane. Each
``run()`` appends a record to ``occupancy_log`` — the executor's decision
log — with the run's fused-segment occupancy (slot-busy fraction,
in-segment admissions per segment, bubble slot-steps) and its preemption
/ pressure-stall counts under optimistic admission, so the control plane
can see both how densely the data plane is packing its hardware and what
that packing cost in preempted work. ``ExecRequest.slo`` threads each
query's latency objective down to the engine's SLO-aware victim choice,
and ``ExecRequest.on_report`` carries the degradation verdict (was any
of this query's work preempted?) back up to the worker.
"""
from __future__ import annotations

import dataclasses
import itertools
import threading
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Tuple

import numpy as np

from repro.configs.base import ArchConfig
from repro.core import profiler as prof
from repro.core.abstraction import Variant
from repro.core.worker import ExecRequest
from repro.serving.engine import Request, ServingEngine


@dataclasses.dataclass
class EngineExecutorConfig:
    """Engine geometry + synthetic request shape for real execution."""
    max_batch: int = 4          # engine slots (admission queues past this)
    max_len: int = 32
    decode_block: int = 4
    min_bucket: int = 4
    prompt_len: int = 6         # synthetic request shape (fixed -> one
    max_new: int = 3            # prefill bucket, zero steady-state compiles)
    refit_min_points: int = 2   # distinct batch sizes before an m,c refit
    obs_window: int = 32        # measurements kept per (variant, batch)
    seed: int = 0
    page_size: Optional[int] = None   # paged KV cache (None = contiguous)
    n_pages: Optional[int] = None     # pool size (None = slot parity)
    chunk_threshold: Optional[int] = None  # chunked prefill past this len
    max_engines: Optional[int] = None  # LRU cap on live engines (None = off)
    stage_slots: int = 0              # in-segment admission ring (0 = off)
    admission: str = "worstcase"      # page admission: worstcase|optimistic
    preempt_policy: str = "slack"     # pressure victim choice: slack|lru
    prefix_cache: bool = False        # page-granular prompt-prefix sharing
    prefix_evict: str = "lru"         # cached-page eviction: lru|fifo
    stream: bool = False              # per-segment partial outputs through
    #                                   ExecRequest.on_tokens (TTFT)
    quantize: str = "none"            # "int8": serve every dense-family
    #                                   engine with weight-only int8 params
    #                                   (jax-int8 variants quantize even
    #                                   when this stays "none")
    speculate: Optional[str] = None   # "DRAFT:K": speculative decoding —
    #                                   draft arch DRAFT ("int8" = the
    #                                   int8 variant of the same arch)
    #                                   proposes K tokens per round
    swap: Optional[str] = None        # "host": preempted KV pages park in
    #                                   host RAM and scatter back at
    #                                   re-admission (zero replay compute)
    swap_budget_bytes: Optional[int] = None  # cap on parked swap payloads
    deadline_enforce: bool = False    # cancel engine slots whose query is
    #                                   already past its SLO deadline
    #                                   (threaded runtime only)
    faults: Optional[Any] = None      # serving.faults.FaultInjector —
    #                                   consulted at the executor/stepper
    #                                   fault sites; None = no chaos


class EngineExecutor:
    """Real executor: worker jobs run on per-variant ``ServingEngine``s.

    ``arch_cfgs`` maps architecture name -> the ``ArchConfig`` to build; pass
    a shared ``model_cache`` dict to reuse built params across executors
    (one per worker) in the same cluster.
    """

    def __init__(self, arch_cfgs: Dict[str, ArchConfig],
                 cfg: EngineExecutorConfig = EngineExecutorConfig(),
                 model_cache: Optional[Dict[str, Tuple[Any, Any]]] = None):
        self.arch_cfgs = dict(arch_cfgs)
        self.cfg = cfg
        self.engines: Dict[str, ServingEngine] = {}      # by variant name
        # bounded per-(variant, batch) history: refits stay O(obs_window)
        # per job and memory stays flat in a long-running cluster
        self.observations: Dict[str, Dict[int, Deque[float]]] = {}
        self.refits: Dict[str, int] = {}                 # refit count
        self.evictions = 0                               # LRU engine drops
        # per-run occupancy records of the synchronous ``run`` path (the
        # executor's decision log): how full the fused segments ran, and
        # how many requests in-segment admission packed into them.
        # Bounded like `observations` so a long-running cluster's memory
        # stays flat.
        self.occupancy_log: Deque[Dict[str, Any]] = \
            deque(maxlen=max(cfg.obs_window * 8, 256))
        # monotone total of the engines' pressure events (preemptions +
        # stalls), on both the synchronous and the threaded path. The
        # master's circuit breaker diffs this across sweeps; the bounded
        # deque above is no good for that (entries fall off the left once
        # it wraps, so positional bookkeeping drifts).
        self.pressure_events_total: float = 0.0
        self._models = model_cache if model_cache is not None else {}
        self._rid = itertools.count()
        # serializes run() (engines, observations, occupancy_log): the
        # wall-clock runtime's stepper thread and direct callers may race
        self._lock = threading.RLock()

    # ------------------------------------------------------------------
    def _model(self, arch: str, quantize: str = "none"):
        key = arch if quantize == "none" else f"{arch}::{quantize}"
        entry = self._models.get(key)
        if entry is None:
            import jax
            from repro.models.model import build_model
            cfg = self.arch_cfgs[arch]
            if quantize == "none":
                model = build_model(cfg)
                params = model.init(jax.random.PRNGKey(self.cfg.seed))
            else:
                # quantized variants share the fp build's weights: the
                # int8 tree is derived from (not trained separately from)
                # the arch's params, so the fp32/int8 pair differ only by
                # per-channel weight rounding — the accuracy/latency axis
                # selection trades over. The fp tree is cached only when
                # an fp engine asked for it; otherwise it is rebuilt from
                # the same seed (same values) and dropped once quantized:
                # one 16 GB chip cannot keep phi3-mini's bf16 tree next to
                # its int8 tree and still seat a KV cache.
                from repro.models.quantize import quantize_params_dense
                fp = self._models.get(arch)
                base_params = fp[1] if fp is not None else \
                    build_model(cfg).init(jax.random.PRNGKey(self.cfg.seed))
                model = build_model(dataclasses.replace(cfg,
                                                        quantize=quantize))
                params = quantize_params_dense(base_params)
            entry = (model, params)
            self._models[key] = entry
        return entry

    def _spec_kwargs(self, variant: Variant) -> Dict[str, Any]:
        """ServingEngine speculate kwargs for ``cfg.speculate`` ("DRAFT:K").

        DRAFT is "int8" (the int8-quantized variant of the same arch — a
        cheap draft whose argmaxes track the fp target closely) or another
        arch name from ``arch_cfgs``. Families the engine can't speculate
        on (and int8 drafts of non-dense archs) silently skip speculation
        rather than failing the whole variant."""
        if not self.cfg.speculate:
            return {}
        arch_cfg = self.arch_cfgs[variant.arch]
        if arch_cfg.family not in ("dense", "hybrid", "ssm"):
            return {}
        name, _, ks = self.cfg.speculate.partition(":")
        spec_k = int(ks) if ks else 4
        if name == "int8":
            if arch_cfg.family != "dense":
                return {}
            dm, dp = self._model(variant.arch, "int8")
        else:
            dm, dp = self._model(name)
            if dm.cfg.family != "dense" or dm.cfg.vocab != arch_cfg.vocab:
                return {}
        return {"speculate": (dm, dp), "spec_k": spec_k}

    def _engine(self, variant: Variant) -> ServingEngine:
        eng = self.engines.pop(variant.name, None)
        if eng is None:
            if self.cfg.max_engines is not None:
                # LRU cap: multi-arch real clusters stay host-sized.
                # Engines are idle between run() calls, so eviction never
                # drops in-flight state; an evicted variant rebuilds
                # lazily here and re-warms before the measured window.
                while len(self.engines) >= max(self.cfg.max_engines, 1):
                    victim = next(iter(self.engines))
                    del self.engines[victim]
                    self.evictions += 1
            # int8 variants (framework "jax-int8", or a blanket
            # cfg.quantize="int8") serve weight-only quantized params —
            # dense-family only; other families keep their fp build
            arch_cfg = self.arch_cfgs[variant.arch]
            use_int8 = (arch_cfg.family == "dense"
                        and (variant.framework == "jax-int8"
                             or self.cfg.quantize == "int8"))
            model, params = self._model(
                variant.arch, "int8" if use_int8 else "none")
            kwargs = self._spec_kwargs(variant)
            # xLSTM has no attention KV to page and chunked prefill /
            # in-segment admission are engine-gated per family (the
            # engine clamps the knobs itself)
            if self.cfg.page_size is not None:
                kwargs.update(page_size=self.cfg.page_size,
                              n_pages=self.cfg.n_pages)
            if self.cfg.swap is not None:
                kwargs.update(
                    swap=self.cfg.swap,
                    swap_budget_bytes=self.cfg.swap_budget_bytes)
            eng = ServingEngine(
                model, params,
                max_batch=min(self.cfg.max_batch,
                              max(variant.profile.max_batch, 1)),
                max_len=self.cfg.max_len,
                decode_block=self.cfg.decode_block,
                min_bucket=self.cfg.min_bucket,
                chunk_threshold=self.cfg.chunk_threshold,
                stage_slots=self.cfg.stage_slots,
                admission=self.cfg.admission,
                preempt_policy=self.cfg.preempt_policy,
                prefix_cache=self.cfg.prefix_cache,
                prefix_evict=self.cfg.prefix_evict,
                stream=self.cfg.stream,
                **kwargs)
            eng.warmup(prompt_lens=[self.cfg.prompt_len])
        # dict order doubles as the LRU list: reinsert on every access
        self.engines[variant.name] = eng
        return eng

    # ------------------------------------------------------------------
    def _synthetic_prompt(self, vocab: int) -> np.ndarray:
        return (np.arange(self.cfg.prompt_len, dtype=np.int64)
                % vocab).astype(np.int32)

    _OCC_KEYS = ("busy_slot_steps", "bubble_slot_steps",
                 "inseg_admissions", "decode_dispatches",
                 "preemptions", "pressure_stalls",
                 "prefix_hits", "prefix_pages_reused", "cow_copies",
                 "evictions",
                 "spec_rounds", "spec_proposed", "spec_accepted",
                 "swap_out", "swap_in", "swap_fallbacks")

    def _make_requests(self, er: ExecRequest, vocab: int,
                       t0: float) -> List[Request]:
        """One engine Request per payload prompt (or synthetic stand-in)."""
        ers: List[Request] = []
        if er.prompts:
            for p in er.prompts:
                ers.append(Request(
                    rid=next(self._rid),
                    prompt=np.asarray(p, np.int32),
                    max_new_tokens=max(er.max_new_tokens, 1),
                    arrival=t0, slo=er.slo, qid=er.qid))
        else:
            for _ in range(max(er.n_inputs, 1)):
                ers.append(Request(
                    rid=next(self._rid),
                    prompt=self._synthetic_prompt(vocab),
                    max_new_tokens=self.cfg.max_new, arrival=t0,
                    slo=er.slo, qid=er.qid))
        return ers

    def _pump_stream(self, eng: ServingEngine,
                     sinks: Dict[int, Tuple[ExecRequest, int]]) -> int:
        """Forward freshly harvested partial outputs to their queries'
        ``on_tokens`` sinks (no-op on non-streaming engines). Returns the
        number of chunks delivered."""
        if not eng.stream:
            return 0
        n = 0
        for r, toks, t in eng.drain_partial_outputs():
            ent = sinks.get(id(r))
            if ent is not None:
                er, idx = ent
                if er.on_tokens is not None:
                    er.on_tokens(idx, toks, t)
                    n += 1
        return n

    def _record_occupancy(self, variant: Variant, batch: int, dt: float,
                          occ0: Dict[str, int],
                          eng: ServingEngine) -> None:
        # decision-log entry: per-run occupancy of the fused segments
        d = {k: eng.stats.get(k, 0) - occ0[k] for k in occ0}
        total = d["busy_slot_steps"] + d["bubble_slot_steps"]
        segs = d["decode_dispatches"]
        self.occupancy_log.append({
            "variant": variant.name, "batch": int(batch),
            "service_s": dt, "segments": segs,
            "slot_busy_frac":
                d["busy_slot_steps"] / total if total else 0.0,
            "admissions_per_segment":
                d["inseg_admissions"] / segs if segs else 0.0,
            "bubble_slot_steps": d["bubble_slot_steps"],
            "preemptions": d["preemptions"],
            "pressure_stalls": d["pressure_stalls"],
            # prefix-cache counters (all zero with the cache off): the
            # hit rate here is what model selection / autoscaling can
            # later key on to co-locate shared-prefix traffic
            "prefix_hits": d["prefix_hits"],
            "prefix_pages_reused": d["prefix_pages_reused"],
            "cow_copies": d["cow_copies"],
            "evictions": d["evictions"],
            # speculative-decoding yield for this run (zeros with
            # speculation off): acceptance rate is the control plane's
            # signal for whether the draft is earning its dispatches
            "spec_rounds": d["spec_rounds"],
            "spec_accept_rate":
                d["spec_accepted"] / d["spec_proposed"]
                if d["spec_proposed"] else 0.0,
            # host-RAM swap activity (zeros with swap off): swap-in vs
            # fallback counts show how often preemption recovery avoided
            # a full prefix replay
            "swap_out": d["swap_out"],
            "swap_in": d["swap_in"],
            "swap_fallbacks": d["swap_fallbacks"],
        })
        self.pressure_events_total += \
            d["preemptions"] + d["pressure_stalls"]

    @staticmethod
    def _deliver(er: ExecRequest, ers: List[Request]) -> None:
        """Hand a finished group's tokens and degradation report back."""
        if er.on_outputs is not None:
            er.on_outputs([np.asarray(r.tokens, np.int32) for r in ers])
        if er.on_report is not None:
            # degradation report back to the control plane: a query
            # whose requests were preempted (and recovered) completed
            # degraded — identical tokens, borrowed time
            npre = sum(r.preemptions for r in ers)
            er.on_report({"preemptions": npre, "degraded": npre > 0,
                          "timed_out": any(
                              getattr(r, "cancelled", False) for r in ers)})

    def step_latency(self, variant: Variant) -> float:
        """Measured wall seconds of one decode step on the variant's
        engine, queue excluded (``ServingEngine.step_seconds``): what the
        overload test sets against one step of the profile's t(b). 0.0
        before the engine's first step."""
        eng = self.engines.get(variant.name)
        return eng.step_seconds if eng is not None else 0.0

    def _observe(self, variant: Variant, n: int, dt: float) -> None:
        """Fold one synthetic-batch measurement into the t(b) fit."""
        obs = self.observations.setdefault(variant.name, {})
        obs.setdefault(n, deque(maxlen=self.cfg.obs_window)).append(dt)
        if prof.refit_profile(variant.profile, obs,
                              min_points=self.cfg.refit_min_points):
            self.refits[variant.name] = \
                self.refits.get(variant.name, 0) + 1

    def run(self, variant: Variant, batch: int,
            requests: Optional[List[ExecRequest]] = None) -> float:
        """Serve one batch for real — each ExecRequest's payload prompts
        (or synthetic stand-ins) become engine Requests; return the
        measured service time, hand generated tokens back through each
        request's ``on_outputs`` sink, and fold the measurement into the
        variant's profile. With ``cfg.stream`` set, partial outputs are
        forwarded to each request's ``on_tokens`` sink after every engine
        step (synchronously, in emission order)."""
        with self._lock:
            eng = self._engine(variant)
            vocab = self.arch_cfgs[variant.arch].vocab
            if not requests:
                requests = [ExecRequest(n_inputs=max(int(batch), 1))]
            # compile any new prompt buckets outside the measured window,
            # so a first-seen payload length doesn't bill XLA compile time
            # as service time
            real_lens = [len(p) for er in requests for p in er.prompts]
            if real_lens:
                eng.warmup(prompt_lens=real_lens)
            groups: List[Tuple[ExecRequest, List[Request]]] = []
            # .get: duck-typed engine stand-ins need not carry every
            # counter (absent == zero, e.g. spec_* with no draft wired)
            occ0 = {k: eng.stats.get(k, 0) for k in self._OCC_KEYS}
            t0 = time.perf_counter()
            sinks: Dict[int, Tuple[ExecRequest, int]] = {}
            for er in requests:
                ers = self._make_requests(er, vocab, t0)
                for i, r in enumerate(ers):
                    eng.submit(r)
                    sinks[id(r)] = (er, i)
                groups.append((er, ers))
            while eng.busy:
                eng.step()
                self._pump_stream(eng, sinks)
            eng.drain_completions()
            dt = time.perf_counter() - t0
            self._record_occupancy(variant, batch, dt, occ0, eng)
            for er, ers in groups:
                self._deliver(er, ers)
            # only synthetic runs calibrate t(b): they share one fixed
            # (prompt_len, max_new) shape, so duration varies with batch
            # count alone. Payload runs have arbitrary prompt/decode shapes
            # and would corrupt the shared m/c fit that selection and
            # autoscaling plan with (same hazard JaxExecutor.measured keys
            # by prompt_len to avoid).
            if not any(er.prompts for er in requests):
                n = max(sum(len(ers) for _, ers in groups), 1)
                self._observe(variant, n, dt)
            return dt
