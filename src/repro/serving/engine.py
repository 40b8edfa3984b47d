"""Real-execution serving data plane: continuous-batching, device-resident
decode engine with shape bucketing.

This is the data plane behind a ``JaxExecutor`` worker: the INFaaS control
plane picks the variant; this engine actually runs it. The design replaces
the seed's run-to-completion waves (one device dispatch *and one host sync
per generated token*, one XLA compile per distinct ``(batch, prompt_len)``)
with three mechanisms:

**Slot scheduler (continuous batching).** The engine owns a preallocated
max-shape KV cache of ``max_batch`` slots x ``max_len`` positions plus
per-slot ``tok``/``pos``/``remaining`` arrays, all device-resident. A
request is admitted by prefilling its prompt (batch 1, right-padded to a
bucket) and inserting the resulting cache into a free slot via
``dynamic_update_slice`` along each leaf's batch axis — there is no
post-prefill ``_pad_cache`` copy of the whole batch. Slots are freed the
moment their sequence finishes and refilled from the pending queue between
decode segments, so short requests never wait for the longest request in a
wave.

**Fused decode segments.** Decoding runs as a ``lax.while_loop`` over
``model.decode`` inside one jitted function: up to ``decode_block`` tokens
for all slots are generated in a single device dispatch with a single
host sync at the end (the seed engine synced every token). Each slot
carries its own position vector (``decode``'s per-sequence ``pos``) and an
activity mask; finished slots stop advancing, and the loop exits early
when every slot is done, so drained batches stop costing FLOPs.

**Shape bucketing + warmup.** Prompt lengths are padded up to power-of-two
buckets (>= ``min_bucket``, <= ``max_len``) and admit batches are bucketed
to {1, max_batch} (same-bucket prompts admitted in one dispatch; padding
rows scatter out of bounds and are dropped), with prefill executables
keyed on the (bucket_batch, bucket_len) pair — a mixed-length request
stream compiles at most two prefills per prompt bucket and exactly one
decode-segment program per engine.
``warmup(prompt_lens=...)`` triggers those compiles eagerly so calibration
(``JaxExecutor``) and latency-sensitive serving never pay compile time
inside a measured service time. ``stats`` counts actual retraces
(``prefill_traces`` / ``decode_traces``), which tests pin down.

**Paged KV cache (block tables).** With ``page_size=None`` (default) every
slot owns a contiguous ``max_len`` run of KV positions, so slot count is
bound by worst-case context length even when most requests are short —
exactly the over-provisioning INFaaS's model-level autoscaling argues
against. With ``page_size=P`` the attention cache becomes a shared page
pool ``(L, n_pages, P, K, D)`` plus a per-slot block table
(``repro.models.kvcache``): admission is gated on *free pages* (a request
reserves ``ceil((prompt + max_new - 1) / P)`` pages, its worst case) rather
than free max-shape slots, pages are appended to a slot's block table as
its ``pos`` crosses a page boundary (topped up ahead of each decode
segment) and returned to the free list the moment the sequence finishes.
``n_pages`` defaults to ``max_batch * max_len / page_size`` (capacity
parity); provisioning fewer pages than slots-worth is the point — a
long-tail stream of mostly-short requests runs ``n_pages * P / max_len``-
slot hardware at far higher concurrency. Recurrent families' O(1) states
(SSM/conv/xLSTM) have no sequence axis and stay slot-indexed; greedy
outputs are bit-identical to the contiguous engine (the gathered view an
attention step sees is position-for-position the same tensor).

**Chunked prefill.** A long prompt's monolithic prefill dispatch used to
stall every in-flight decode for the whole prompt length. With
``chunk_threshold=T`` set, prompts longer than ``T`` skip the prefill
dispatch entirely: the prompt is staged in a device-resident per-slot
prompt buffer and *teacher-forced through the fused decode segment* —
each segment consumes up to ``decode_block`` prompt tokens for that slot
(writing KV, discarding logits until the prompt is exhausted, then
switching to greedy emission) while other slots keep generating in the
same dispatch. A near-``max_len`` prompt admitted mid-stream therefore
delays in-flight decodes by zero extra dispatches. Chunked admission is
enabled for the dense/hybrid/ssm families — each slot restarts from the
family's empty decode state via ``Model.empty_state`` (all-zeros, except
xLSTM's -inf stabilizers). Audio/vlm need encoder KV from prefill, and
MoE's expert-capacity keep/drop decisions depend on the co-batched token
set (prompt tokens fed inside the shared decode batch would diverge from
the solo prefill the engine guarantees), so those families admit whole
prompts regardless of the knob.

**In-segment admission (staging ring).** Even with chunked prefill, a slot
that finishes mid-segment idles until the ``lax.while_loop`` exits, and a
newly arrived request waits for the next ``step()`` boundary — the
occupancy bubble that inflates tail latency under bursty short-request
load. With ``stage_slots=N`` the engine keeps a device-resident staging
ring of up to ``N`` pending requests (prompt rows, lengths, ``max_new``,
and — in paged mode — pre-reserved block-table rows): the decode loop's
carry tracks a ring head, and the moment a slot's ``rem`` hits zero
mid-segment the loop records the completion in a per-slot completion log
and pulls the next staged request into the freed slot — resetting
``pos``/``rem``/``plen``/prompt-buffer pointers, restoring the slot's O(1)
recurrent-state rows to the family's empty state
(``Model.empty_state`` — xLSTM's stabilizers start at -inf, not zero),
and switching the slot to the staged request's block-table row. One
dispatch can therefore retire *multiple* requests per slot with zero
extra dispatches or host syncs; the host decodes the completion log after
the segment to split each slot's emission row between its successive
occupants. Staged requests teacher-force their prompts through the fused
segment exactly like chunked prefill, so in-segment admission is gated to
the same families whose teacher-forced decode is exact from the empty
state (dense/hybrid/ssm); other families clamp ``stage_slots`` to 0 and
keep boundary-only admission. In paged mode a staged request holds its
worst-case page reservation from staging time (its first
``decode_block`` positions' pages are materialized up front, since no
host boundary can top it up mid-segment); ``PageAllocator`` tracks these
staged reservations under per-request tickets that are re-keyed to the
slot at harvest.

**Optimistic admission + SLO-aware preemption.** Worst-case admission
(``admission="worstcase"``, the default) reserves every request's full
``ceil((prompt + max_new - 1) / P)`` pages up front, so the pool is
chronically under-committed: the decode tail is reserved long before it is
written, and the only failure mode under pressure is head-of-line
queueing. ``admission="optimistic"`` admits on *expected* usage instead —
a prefill request needs its prompt pages now (they are scattered at the
prefill dispatch) and a chunked request only its first ``decode_block``
stride — and grows the decode tail lazily. When the pool runs dry at a
growth point (a live slot's ``pos`` is about to cross a page boundary
with zero free pages — at the segment-boundary top-up, or because staged
in-segment refills hold pages), the engine *preempts* instead of wedging:
staged-but-unstarted requests are un-staged first (zero work lost), then
a live victim is chosen, its pages freed, and the request parked host-side
with its prompt plus every token generated so far. Re-admission
teacher-forces that full prefix through the chunked-prefill path, so
recovery is **bit-identical** to an uninterrupted run (greedy decode is
deterministic given the prefix). Victim choice is SLO-aware
(``preempt_policy="slack"``): each ``Request`` carries its latency
objective (``slo``), and the engine preempts the request with the most
slack — deadline minus elapsed minus estimated remaining (segment-time
EWMA x positions left) — treating no-SLO requests as infinite slack and
breaking ties toward longest-remaining; ``preempt_policy="lru"`` preempts
the most recently admitted request instead (vLLM-style recompute).
Optimistic admission requires the paged layout and a family whose
teacher-forced decode is exact from the empty state (dense/hybrid/ssm);
other configurations clamp back to worst-case. ``stats`` counts
``preemptions``, ``preempt_readmits`` and ``pressure_stalls`` (growth
points that found the pool dry), and each ``Request`` counts its own
``preemptions`` so callers can surface a ``degraded`` flag.

**Occupancy accounting.** ``stats`` tracks ``busy_slot_steps`` /
``bubble_slot_steps`` (active vs idle slot-steps inside fused segments,
counted in the loop carry), ``inseg_admissions`` and ``staged``; the
``occupancy`` property derives the per-segment slot-busy fraction and
admissions-per-segment that ``EngineExecutor`` threads into its
decision log.

**Open-loop core.** The engine is step-driven: state (slot occupancy,
pending queue, per-slot generations) persists on the engine, and the three
phases of the serving loop are separately callable —

* ``submit(req)``     enqueue a request (at any time, including while other
  requests are mid-decode); its latency clock starts at ``Request.arrival``
  (stamped at submit if unset),
* ``step()``          admit pending requests into free slots, run ONE fused
  decode segment, harvest finished slots,
* ``drain_completions()``  collect requests finished since the last drain.

Mid-stream admission falls out: a request submitted between segments joins
the next ``step()`` without restarting in-flight slots. ``serve()`` is a
thin closed loop over the core (submit all, step until idle) and produces
bit-identical outputs and identical trace/dispatch counts to the closed
PR-1 loop. The open seam is what lets the INFaaS control plane
(``EngineExecutor`` in ``repro.serving.executor``) drive real engines.

Exactness: for the dense/hybrid/ssm (and, by the same causal-masking
argument, vlm) families the engine emits token-for-token the same greedy
outputs as a serial per-request prefill+decode (prompts are right-padded;
causal attention masks padded KV via per-sequence valid lengths, and
recurrent families mask their state updates — see ``repro.models.model``).
MoE matches serial decode except when GShard-style expert capacity —
a static function of the padded token count — crosses a boundary between
the prompt's bucket and its exact length and flips a token-drop decision
(see ``prefill_moe``); MoE prompts are therefore admitted one per
dispatch, which keeps decode exact and confines the effect to prefill.
The audio family masks its encoder self-attention and decoder
cross-attention by each request's true encoder length (threaded through
the cache as a per-slot ``enc_len``), so padded encoder rows contribute
exact zeros: audio outputs are padding-independent, and the paged layout
(whose dropped writes leave padding rows stale) is bit-identical to
contiguous for audio too.

The seed wave engine survives as ``WaveEngine`` — the benchmark baseline
for ``benchmarks/fig_engine_throughput.py``.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.configs.base import ArchConfig
from repro.core import spans
from repro.models import kvcache as KV
from repro.models.model import Model, build_model


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                  # (prompt_len,) int32
    max_new_tokens: int = 8
    arrival: float = 0.0
    tokens: Optional[np.ndarray] = None
    latency: float = 0.0
    # wall time the request entered a device slot (prefill, chunked, or
    # in-segment promotion at harvest); admitted - arrival is queue delay
    admitted: float = -1.0
    # per-query latency objective in seconds (deadline = arrival + slo);
    # None = best-effort. Drives SLO-aware victim choice under pressure.
    slo: Optional[float] = None
    # times this request was preempted (pages freed, parked, prefix
    # replayed); > 0 lets callers surface a "degraded" flag on results
    preemptions: int = 0
    # streaming cursor: tokens [0, streamed) were already handed out via
    # ``drain_partial_outputs`` — survives preempt/replay, so a re-admitted
    # request never re-streams tokens it delivered before parking
    streamed: int = 0
    # wall time the first generated token was harvested (segment
    # granularity); -1 until it happens. first_token - arrival is TTFT.
    first_token: float = -1.0
    # set by ``ServingEngine.cancel``: the request was cut short (deadline
    # enforcement) and ``tokens`` holds only what was generated by then
    cancelled: bool = False
    qid: int = -1            # the control plane's query id (for spans)


@dataclasses.dataclass
class _Parked:
    """A preempted request parked host-side awaiting re-admission."""
    req: Request
    prefix: np.ndarray      # prompt + every token generated before preempt
    done: List[int]         # tokens already generated (re-credited at seat)
    # host-RAM KV swap payload (``swap="host"``): per-leaf host copies of
    # the victim's live pages, in block-table order. None = recompute
    # recovery (swap off, gated out, or over budget).
    kv: Optional[Any] = None
    swap_bytes: int = 0     # budget charge released at swap-in


def bucket_len(n: int, minimum: int = 8, maximum: Optional[int] = None) -> int:
    """Round ``n`` up to a power of two >= ``minimum`` (clamped to maximum)."""
    b = max(minimum, 1 << max(int(n) - 1, 0).bit_length())
    if maximum is not None:
        if n > maximum:
            raise ValueError(f"length {n} exceeds engine max_len {maximum}")
        b = min(b, maximum)
    return b


class PageAllocator:
    """Host-side accounting for the shared KV page pool.

    Admission reserves a holder's worst case (``ceil(n_positions /
    page_size)`` pages for ``prompt_len + max_new - 1`` written positions)
    so a decode can never strand mid-stream for lack of pages — ``cover()``
    calls, which lazily hand out physical pages as ``pos`` grows, always
    succeed within the reservation. Holders are arbitrary hashable keys:
    the engine keys live slots by slot index and staged-but-unadmitted
    requests (in-segment admission) by per-request tickets, re-keyed to
    the slot via ``rekey()`` when the staging ring promotes them.
    Invariants (pinned by the hypothesis property test): no page is ever
    held by two live holders, ``free + staged + live == n_pages`` at all
    times, and a full drain returns every page to the free list.

    **Prefix sharing** relaxes "no page held by two holders" into
    refcounting: ``attach()`` points an additional holder at pages some
    other holder (or the prefix cache) already owns, ``release()``
    decrements and only a count of zero returns the page — either to the
    free list or, when the ``retain`` hook claims it (the prefix cache
    retains pages it has indexed), to a *cached* pool of reclaimable
    rc==0 pages. ``cover()`` and ``cow()`` fall back to evicting a cached
    page (``evict_choice`` picks, ``on_evict`` notifies the index) when
    the free list runs dry, so caching never reduces usable capacity.
    The sharing-era invariants, pinned by the extended property test:
    ``free + cached + unique_live == n_pages``, a page's refcount equals
    the number of holders listing it, and eviction only ever takes rc==0
    pages.
    """

    def __init__(self, n_pages: int, page_size: int):
        if n_pages < 1 or page_size < 1:
            raise ValueError(f"bad pool: {n_pages} pages x {page_size}")
        self.n_pages = n_pages
        self.page_size = page_size
        self._free: List[int] = list(range(n_pages))[::-1]
        self._pages: Dict[Any, List[int]] = {}     # holder -> held page ids
        self._reserved: Dict[Any, int] = {}        # holder -> worst case
        self._refcnt: Dict[int, int] = {}          # page -> live holders
        self._cached: Dict[int, None] = {}         # rc==0 retained pages
        # prefix-cache seams (all optional): ``retain(page) -> bool``
        # claims an rc==0 page for the cached pool instead of the free
        # list; ``evict_choice() -> page`` picks which cached page to
        # reclaim under free-list pressure; ``on_evict(page)`` tells the
        # index the page's contents are about to be overwritten.
        self.retain = None
        self.evict_choice = None
        self.on_evict = None
        self.evictions = 0

    def pages_needed(self, n_positions: int) -> int:
        return max(0, -(-int(n_positions) // self.page_size))

    @property
    def committed(self) -> int:
        """Pages promised to live slots (held now or claimable later)."""
        return sum(self._reserved.values())

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_cached(self) -> int:
        """rc==0 pages retained by the prefix cache (reclaimable)."""
        return len(self._cached)

    @property
    def n_avail(self) -> int:
        """Pages a cover/cow can actually obtain: free + evictable."""
        return len(self._free) + len(self._cached)

    def refcount(self, page: int) -> int:
        return self._refcnt.get(page, 0)

    def live_pages(self) -> List[int]:
        return [p for pages in self._pages.values() for p in pages]

    def pages_of(self, slot: int) -> List[int]:
        return list(self._pages.get(slot, ()))

    def can_reserve(self, n_positions: int) -> bool:
        return self.committed + self.pages_needed(n_positions) <= self.n_pages

    def reserve(self, slot: Any, n_positions: int,
                strict: bool = True) -> None:
        """Admit ``slot``: commit its worst-case page count (no pages yet).

        ``strict=False`` (optimistic admission) skips the over-commit
        check: the engine admits on expected usage, lets ``committed``
        exceed the pool, and resolves a dry pool by preemption instead of
        up-front refusal."""
        if slot in self._reserved:
            raise ValueError(f"slot {slot} already live")
        need = self.pages_needed(n_positions)
        if strict and self.committed + need > self.n_pages:
            raise ValueError(f"over-committed: {self.committed}+{need} "
                             f"> {self.n_pages}")
        self._reserved[slot] = need
        self._pages[slot] = []

    def can_cover(self, holder: Any, n_positions: int) -> bool:
        """Enough obtainable pages for ``cover(holder, n_positions)``?
        Always true under worst-case admission (the reservation pre-funds
        every cover); optimistic admission uses this as its pressure
        probe. Cached rc==0 pages count — they evict on demand."""
        held = len(self._pages[holder])
        target = min(self.pages_needed(n_positions),
                     self._reserved[holder])
        return target - held <= self.n_avail

    def _grab(self) -> int:
        """One physical page at rc==1: the free list first, then evict a
        cached page (rc==0 by construction, so eviction never frees a
        page any live holder references)."""
        if not self._free:
            page = (self.evict_choice() if self.evict_choice
                    else next(iter(self._cached)))
            del self._cached[page]
            if self.on_evict is not None:
                self.on_evict(page)
            self.evictions += 1
            self._refcnt[page] = 1
            return page
        page = self._free.pop()
        self._refcnt[page] = 1
        return page

    def _deref(self, page: int) -> None:
        self._refcnt[page] -= 1
        if self._refcnt[page] == 0:
            del self._refcnt[page]
            if self.retain is not None and self.retain(page):
                self._cached[page] = None
            else:
                self._free.append(page)

    def cover(self, slot: int, n_positions: int) -> List[int]:
        """Grow ``slot`` to cover positions [0, n); returns the new pages."""
        held = self._pages[slot]
        target = min(self.pages_needed(n_positions), self._reserved[slot])
        grown = []
        while len(held) < target:
            page = self._grab()
            grown.append(page)
            held.append(page)
        return grown

    def attach(self, holder: Any, pages: Sequence[int]) -> None:
        """Point ``holder`` at pages already resident elsewhere (a prefix
        cache hit): each page's refcount grows by one, cached rc==0 pages
        come back live, and the pages count toward the holder's
        reservation exactly like pages it covered itself."""
        held = self._pages[holder]
        for p in pages:
            if p in self._cached:
                del self._cached[p]
            self._refcnt[p] = self._refcnt.get(p, 0) + 1
            held.append(p)

    def cow(self, holder: Any, idx: int) -> Tuple[int, int]:
        """Copy-on-write ``holder``'s ``idx``-th page: grab a private
        page at rc==1, swap it into the holder's list, and drop the
        holder's reference to the shared original. Returns ``(shared,
        private)``; the caller copies the page's device contents."""
        held = self._pages[holder]
        old = held[idx]
        new = self._grab()
        held[idx] = new
        self._deref(old)
        return old, new

    def release(self, slot: int) -> List[int]:
        """Drop all of ``slot``'s page references (sequence finished or
        preempted). Pages nobody else references return to the pool —
        free list, or the prefix cache's cached set when indexed."""
        pages = self._pages.pop(slot)
        del self._reserved[slot]
        for p in pages:
            self._deref(p)
        return pages

    def rekey(self, old: Any, new: Any) -> None:
        """Transfer a reservation (and its held pages) to a new holder key:
        a staged request's ticket becomes the slot it was pulled into."""
        if new in self._reserved:
            raise ValueError(f"holder {new!r} already live")
        self._reserved[new] = self._reserved.pop(old)
        self._pages[new] = self._pages.pop(old)


class PrefixCache:
    """Host-side prefix index over the shared KV page pool.

    Prompts are hashed at page granularity with a *chained* digest:
    ``h_i = sha1(h_{i-1} || tokens[i*ps : (i+1)*ps])``, so a page's hash
    commits to every token before it and equal chains imply equal
    logical prefixes (sha1 collisions aside — python ``hash()`` would
    serve wrong tokens on collision, a cryptographic digest won't).
    ``register()`` maps a chain digest to the physical page holding that
    page's KV once the page is fully written with prompt tokens;
    ``lookup()`` walks a new prompt's chain and returns the longest run
    of fully-indexed pages, which admission attaches to the new slot's
    block table (refcounted — the pages are never written by the sharer;
    a write landing inside a shared page triggers copy-on-write first).

    Pages stay indexed while live (rc >= 1) and move to the allocator's
    *cached* pool when their last holder releases them; a cached page is
    reclaimed (and unindexed, via ``on_evict``) only when the free list
    runs dry. ``policy="lru"`` evicts the page whose last release is
    oldest; ``policy="fifo"`` evicts in registration order.
    """

    def __init__(self, alloc: PageAllocator, page_size: int,
                 policy: str = "lru"):
        if policy not in ("lru", "fifo"):
            raise ValueError(f"unknown prefix eviction policy {policy!r}")
        self._alloc = alloc
        self.page_size = page_size
        self.policy = policy
        self._index: Dict[bytes, int] = {}       # chain digest -> page
        self._hash_of: Dict[int, bytes] = {}     # page -> chain digest
        self._reg_seq: Dict[int, int] = {}       # page -> registration no.
        self._seq = 0
        alloc.retain = self._retain
        alloc.on_evict = self._on_evict
        alloc.evict_choice = self._evict_choice

    # ---- allocator seams --------------------------------------------
    def _retain(self, page: int) -> bool:
        return page in self._hash_of

    def _on_evict(self, page: int) -> None:
        h = self._hash_of.pop(page)
        del self._index[h]
        del self._reg_seq[page]

    def _evict_choice(self) -> int:
        cached = self._alloc._cached
        if self.policy == "fifo":
            return min(cached, key=lambda p: self._reg_seq[p])
        return next(iter(cached))       # dict order == release recency

    # ---- hashing ----------------------------------------------------
    def chain(self, tokens: np.ndarray) -> List[bytes]:
        """Chained page digests of every *full* page of ``tokens``."""
        import hashlib
        ps = self.page_size
        toks = np.ascontiguousarray(np.asarray(tokens), np.int32)
        h, out = b"", []
        for i in range(len(toks) // ps):
            h = hashlib.sha1(h + toks[i * ps:(i + 1) * ps].tobytes()) \
                .digest()
            out.append(h)
        return out

    # ---- index ------------------------------------------------------
    def lookup(self, tokens: np.ndarray) -> List[int]:
        """Longest indexed page run covering a prefix of ``tokens``."""
        pages = []
        for h in self.chain(tokens):
            page = self._index.get(h)
            if page is None:
                break
            pages.append(page)
        return pages

    def register(self, digests: Sequence[bytes],
                 pages: Sequence[int]) -> None:
        """Index ``pages[i]`` (fully written with the tokens digest
        ``digests[i]`` commits to) for future lookups. A digest already
        indexed keeps its first page — two slots racing the same prompt
        each keep their private copy; one gets shared from now on."""
        for h, p in zip(digests, pages):
            if h in self._index or p in self._hash_of:
                continue
            self._index[h] = p
            self._hash_of[p] = h
            self._reg_seq[p] = self._seq
            self._seq += 1

    def unindex(self, page: int) -> None:
        """Drop ``page`` from the index (it is about to be written in
        place by its sole holder); it re-registers — same digest, same
        contents — once the holder's writes are flushed."""
        h = self._hash_of.pop(page, None)
        if h is not None:
            del self._index[h]
            del self._reg_seq[page]

    def __len__(self) -> int:
        return len(self._index)


class _DraftRunner:
    """Device state of a speculative-decode draft model.

    The draft runs dense-family greedy decode over its own contiguous
    ``(L, max_batch, max_len, K, D)`` KV cache, mirroring the target
    engine's slot layout. ``propose()`` advances every slot ``k`` greedy
    steps in one jitted ``fori_loop`` dispatch and returns the proposed
    tokens; free-slot rows receive garbage writes into their own rows,
    which is harmless (every position is rewritten before a masked read
    can include it — the same stale-KV invariant the engine relies on).

    Per-slot sync is lazy: ``state[s]`` records ``(occupant, pos)`` — the
    request object whose token prefix the draft cache rows ``[0, pos)``
    hold. After a spec round the draft is in sync by construction (its
    accepted rows hold exactly the accepted tokens, and rows past the
    rewind point are rewritten before use), so the common case is a pure
    host-side marker update. A slot whose target advanced without the
    draft (a normal decode segment, admission, preempt/replay) simply
    mismatches the marker and is resynced by one bucketed prefill whose
    cache slab is scattered into the slot's row.
    """

    def __init__(self, model: Model, params: Any, max_batch: int,
                 max_len: int, k: int, min_bucket: int = 8):
        if model.cfg.family != "dense":
            raise ValueError(
                f"draft model must be dense family, got "
                f"{model.cfg.family!r}")
        self.model = model
        self.params = params
        self.k = int(k)
        self.max_batch = max_batch
        self.max_len = max_len
        self.min_bucket = min_bucket
        shapes = model.cache_shapes(max_batch, max_len)
        self.cache = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), shapes)
        self.state: List[Optional[Tuple[Any, int]]] = [None] * max_batch
        self.stats: Dict[str, int] = {"resyncs": 0, "propose_traces": 0,
                                      "resync_traces": 0}
        self._propose_fn = None
        self._prefill_fns: Dict[int, Any] = {}

    def in_sync(self, slot: int, occupant: Any, pos: int) -> bool:
        st = self.state[slot]
        return st is not None and st[0] is occupant and st[1] == pos

    def resync(self, slot: int, full: np.ndarray, pos: int) -> None:
        """Rebuild the draft KV rows ``[0, pos)`` of ``slot`` from the
        occupant's fed-token prefix ``full`` (prefill right-padded to a
        bucket; pad rows land past ``pos`` and are stale-harmless)."""
        if pos <= 0:
            return
        bucket = bucket_len(pos, self.min_bucket, self.max_len)
        fn = self._prefill_fns.get(bucket)
        if fn is None:
            model = self.model

            def prefill_insert(params, cache, tokens, length, slot_i):
                self.stats["resync_traces"] += 1
                _logits, pc = model.prefill(
                    params, {"tokens": tokens, "length": length})

                def put(c, n):
                    return lax.dynamic_update_slice(
                        c, n.astype(c.dtype), (0, slot_i, 0, 0, 0))

                return jax.tree.map(put, cache, pc)

            fn = jax.jit(prefill_insert)
            self._prefill_fns[bucket] = fn
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :pos] = full[:pos]
        self.cache = fn(self.params, self.cache, toks,
                        np.asarray([pos], np.int32), np.int32(slot))
        self.stats["resyncs"] += 1

    def propose(self, tok, pos):
        """``k`` greedy draft steps for every slot in one dispatch:
        step 0 feeds ``tok`` at ``pos``, each later step feeds its own
        previous argmax. Returns the proposed tokens (B, k) and leaves
        the draft cache advanced through position ``pos + k``.

        The loop runs ``k + 1`` steps: the last step emits nothing new
        but writes the KV row of the final proposal. Without it a fully
        accepted window leaves the draft cache one row short of the
        marker the engine sets (the verify window fed the last proposal
        into the *target* cache, so the stream advances past it), and
        the stale row silently degrades every later round's acceptance
        — worst when the slot's previous occupant left real KV there."""
        if self._propose_fn is None:
            model, k = self.model, self.k

            def propose_k(params, cache, tok, pos):
                self.stats["propose_traces"] += 1
                drafts = jnp.zeros((tok.shape[0], k + 1), jnp.int32)

                def body(i, st):
                    cache, tok, pos, drafts = st
                    logits, cache = model.decode(params, cache, tok, pos)
                    nxt = jnp.argmax(logits[:, -1, :], axis=-1) \
                        .astype(jnp.int32)
                    drafts = lax.dynamic_update_slice(
                        drafts, nxt[:, None], (0, i))
                    return (cache, nxt[:, None], pos + 1, drafts)

                cache, _tok, _pos, drafts = lax.fori_loop(
                    0, k + 1, body, (cache, tok, pos, drafts))
                return cache, drafts[:, :k]

            self._propose_fn = jax.jit(propose_k)
        self.cache, drafts = self._propose_fn(
            self.params, self.cache, tok, pos)
        return drafts


class ServingEngine:
    """Continuous-batching engine over one model + params (greedy decode)."""

    def __init__(self, model: Model, params: Any, max_batch: int = 8,
                 max_len: int = 128, decode_block: int = 16,
                 min_bucket: int = 8, page_size: Optional[int] = None,
                 n_pages: Optional[int] = None,
                 chunk_threshold: Optional[int] = None,
                 stage_slots: int = 0, admission: str = "worstcase",
                 preempt_policy: str = "slack",
                 prefix_cache: bool = False, prefix_evict: str = "lru",
                 stream: bool = False,
                 speculate: Optional[Tuple[Model, Any]] = None,
                 spec_k: int = 4, swap: Optional[str] = None,
                 swap_budget_bytes: Optional[int] = None):
        self.model = model
        self.params = params
        # token streaming: when on, every harvest appends newly generated
        # tokens to a partial-output buffer (drain_partial_outputs) and
        # stamps each request's first-token wall time. Off by default so
        # non-streaming callers never accumulate an undrained buffer.
        self.stream = bool(stream)
        self._partial: List[Tuple[Request, List[int], float]] = []
        self.max_batch = max_batch
        self.max_len = max_len
        self.decode_block = decode_block
        self.min_bucket = min_bucket
        # MoE expert capacity is a function of the co-batched token count,
        # so grouped admission could change token-drop decisions vs a
        # serial run; admit MoE prompts one per dispatch to stay exact.
        self._group_admit = model.cfg.family != "moe"
        # Chunked prefill (and in-segment admission, which reuses the same
        # teacher-forcing path) restarts a slot from the family's empty
        # decode state (``Model.empty_state`` — all-zeros except xLSTM's
        # -inf stabilizers). Families whose prefill computes encoder KV
        # (audio/vlm) admit whole prompts. MoE is excluded too: its
        # expert-capacity keep/drop decisions depend on the co-batched
        # token set, so feeding prompt tokens inside the shared decode
        # batch would diverge from the solo prefill the engine otherwise
        # guarantees (see _group_admit).
        self._chunk_ok = model.cfg.family in ("dense", "hybrid", "ssm")
        self.chunk_threshold = \
            chunk_threshold if self._chunk_ok else None
        # in-segment admission: capacity of the device staging ring
        # (0 = boundary-only admission); clamped off with chunking since
        # staged prompts teacher-force through the decode segment
        self.stage_slots = int(stage_slots) if self._chunk_ok and \
            stage_slots else 0
        self.stats: Dict[str, int] = {
            "prefill_traces": 0, "decode_traces": 0, "chunk_traces": 0,
            "prefill_dispatches": 0, "decode_dispatches": 0,
            # prompt tokens admitted by prefill, and the token rows its
            # dispatches computed (rows x bucket, padding included)
            "prefill_prompt_tokens": 0, "prefill_computed_tokens": 0,
            "decode_steps": 0, "tokens_generated": 0, "admitted": 0,
            "chunk_admits": 0, "peak_concurrency": 0,
            "staged": 0, "inseg_admissions": 0,
            "busy_slot_steps": 0, "bubble_slot_steps": 0,
            "preemptions": 0, "preempt_readmits": 0, "pressure_stalls": 0,
            "prefix_hits": 0, "prefix_pages_reused": 0, "cow_copies": 0,
            "evictions": 0, "prefix_tokens_skipped": 0,
            "spec_rounds": 0, "spec_proposed": 0, "spec_accepted": 0,
            "verify_traces": 0,
            "swap_out": 0, "swap_in": 0, "swap_fallbacks": 0,
            "swap_bytes": 0,
        }
        # ----- host-RAM KV swap-out ------------------------------------
        # ``swap="host"`` preserves a preemption victim's live KV pages in
        # host memory instead of discarding them: re-admission scatters
        # the bytes back into fresh pages — bit-identical continuation
        # with zero replay compute. Bounded by ``swap_budget_bytes``
        # (None = unbounded); anything the gate rejects (non-paged layout,
        # non-pageable leaves, mid-prefix-feed victims, budget exhausted)
        # falls back to the recompute path and counts ``swap_fallbacks``.
        if swap not in (None, "host"):
            raise ValueError(f"unknown swap mode {swap!r}")
        self.swap = swap
        self.swap_budget_bytes = swap_budget_bytes
        self._swap_live_bytes = 0
        # ----- speculative decoding -----------------------------------
        # ``speculate=(draft_model, draft_params)`` replaces each decode
        # round with draft-then-verify: the draft proposes ``spec_k``
        # greedy tokens per live slot, the target verifies all of them in
        # ONE teacher-forced window pass, and the longest prefix whose
        # draft tokens match the target's own greedy argmax is accepted —
        # plus the target's first divergent token, so every round emits at
        # least one genuine token and the output stream is bit-identical
        # to plain greedy decode by construction. Rollback past the first
        # mismatch is a pure (tok, pos) rewind for the dense family
        # (stale KV beyond the rewind point is rewritten before any
        # masked read can include it); recurrent families (hybrid/ssm)
        # verify sequentially through ``decode`` and re-seat the accepted
        # prefix through the teacher-forced replay path on a mismatch,
        # reusing the preemption-recovery primitive.
        self.spec_k = int(spec_k)
        self._spec: Optional["_DraftRunner"] = None
        self._verify_fn = None
        if speculate is not None:
            draft_model, draft_params = speculate
            if not self._chunk_ok:
                raise ValueError(
                    f"family {model.cfg.family!r} cannot speculate: "
                    "mismatch rollback needs the teacher-forced replay "
                    "path (dense/hybrid/ssm only)")
            if getattr(model.cfg, "attention_impl", "xla") \
                    .startswith("pallas"):
                raise ValueError(
                    "speculative decoding requires the XLA attention "
                    "path: the verify window runs outside the fused "
                    "Pallas decode kernel, so bit-identity with plain "
                    "pallas decode would not hold")
            if draft_model.cfg.vocab != model.cfg.vocab:
                raise ValueError(
                    f"draft vocab {draft_model.cfg.vocab} != target "
                    f"vocab {model.cfg.vocab}")
            if self.spec_k < 1:
                raise ValueError(f"spec_k must be >= 1, got {spec_k}")
            self._spec = _DraftRunner(draft_model, draft_params,
                                      max_batch, max_len, self.spec_k,
                                      min_bucket)
            # the staging ring refills slots mid-segment; a spec round has
            # no fused segment to refill in, so admission stays at step()
            # boundaries while speculating
            self.stage_slots = 0
        shapes = model.cache_shapes(max_batch, max_len, enc_len=max_len)
        # Per-leaf batch axis, found by diffing cache shapes at two batch
        # sizes (family-agnostic: attention caches, SSM/conv states, and
        # grouped VLM layouts all place batch differently); per-leaf
        # sequence axis likewise by diffing two max_lens (-1 for the O(1)
        # recurrent states, which have none and are never paged).
        s2 = model.cache_shapes(2, max_len, enc_len=max_len)
        s3 = model.cache_shapes(3, max_len, enc_len=max_len)
        self._batch_axes = jax.tree.map(
            lambda a, b: next(i for i, (x, y) in
                              enumerate(zip(a.shape, b.shape)) if x != y),
            s2, s3)
        l2 = model.cache_shapes(2, max_len + 8, enc_len=max_len + 8)
        self._seq_axes = jax.tree.map(
            lambda a, b: next((i for i, (x, y) in
                               enumerate(zip(a.shape, b.shape)) if x != y),
                              -1),
            s2, l2)
        # ----- paged layout -------------------------------------------
        self.page_size = page_size
        if page_size is not None:
            if max_len % page_size != 0:
                raise ValueError(f"max_len {max_len} not a multiple of "
                                 f"page_size {page_size}")
            self.pages_per_slot = max_len // page_size
            self.n_pages = (max_batch * self.pages_per_slot
                            if n_pages is None else n_pages)
            pageable = any(s != -1 for s in jax.tree.leaves(self._seq_axes))
        else:
            pageable = False
        attn_impl = getattr(model.cfg, "attention_impl", "xla")
        if pageable:
            self._alloc: Optional[PageAllocator] = \
                PageAllocator(self.n_pages, page_size)
            # block-table mirror handed to every device dispatch; the
            # sentinel n_pages drops writes / clamps (masked) reads.
            # The fused Pallas update+attend kernel has no write
            # suppression: instead the pool carries one extra *trash*
            # page at physical index n_pages — exactly the sentinel
            # value — so inactive slots' writes land there harmlessly.
            # The XLA/view path keeps the exact-size pool (scatter uses
            # drop semantics).
            self._pool_pages = self.n_pages + \
                (1 if attn_impl.startswith("pallas") else 0)
            self._bt = KV.sentinel_block_table(
                max_batch, self.pages_per_slot, self.n_pages)
            self._cache = jax.tree.map(
                lambda s, bax, sax: jnp.zeros(
                    self._pool_shape(s.shape, bax, sax), s.dtype),
                shapes, self._batch_axes, self._seq_axes)
        else:
            # contiguous layout — also the path for attention-free
            # families (pure-recurrent xLSTM), whose O(1) states have
            # nothing to page regardless of the knob
            if page_size is None:
                self.pages_per_slot = 0
                self.n_pages = 0
            self._pool_pages = 0
            self._alloc = None
            self._bt = None
            self._cache = jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), shapes)
        self._paged = self._bt is not None
        # ----- admission discipline -----------------------------------
        if admission not in ("worstcase", "optimistic"):
            raise ValueError(f"unknown admission mode {admission!r}")
        if preempt_policy not in ("slack", "lru"):
            raise ValueError(f"unknown preempt policy {preempt_policy!r}")
        # Optimistic admission needs (a) the paged layout — pressure is a
        # page-pool phenomenon — and (b) a family whose teacher-forced
        # decode is exact from the empty state, because recovery replays
        # the preempted prefix through the chunked-prefill seat. Anything
        # else clamps back to worst-case (forced ``preempt()`` still works
        # for any chunk-capable family).
        self.admission = admission if (self._alloc is not None and
                                       self._chunk_ok) else "worstcase"
        self.preempt_policy = preempt_policy
        # ----- prefix cache -------------------------------------------
        # Page-granular prefix sharing needs (a) the paged layout, (b)
        # the teacher-forced seat (a hit resumes the prompt at its first
        # uncached token), and (c) *every* cache leaf position-addressable
        # — an O(1) recurrent state (SSM/conv cells, hybrid's ssm layers)
        # summarizes the whole prefix and cannot be recovered from shared
        # KV pages, so those families clamp the knob off and stay exact.
        # every cache leaf position-addressable (no O(1) recurrent state):
        # the gate shared by the prefix cache and host-RAM KV swap — both
        # reconstruct a slot purely from pool pages
        self._all_paged = all(s != -1
                              for s in jax.tree.leaves(self._seq_axes))
        self._prefix: Optional[PrefixCache] = None
        if prefix_cache and self._paged and self._chunk_ok \
                and self._all_paged:
            self._prefix = PrefixCache(self._alloc, page_size,
                                       policy=prefix_evict)
        # per-slot registration frontier: prompt pages [0, _reg_upto[s])
        # of slot s are already indexed; the chain digests of the slot's
        # seated token row are precomputed at seat time
        self._reg_upto = np.zeros((max_batch,), np.int64)
        self._seat_digests: List[List[bytes]] = [[] for _ in
                                                 range(max_batch)]
        # ----- device mirrors -----------------------------------------
        # The decode segment gathers each slot's KV view from the page
        # pool once at entry and scatters the written span back at exit
        # (XLA layouts), so the per-step loop body is the *contiguous*
        # program: paged indirection costs two transfers per segment
        # instead of two gathers per step. Pallas attention instead runs
        # a fused update+attend kernel over the pool (see
        # kernels.decode_attention.fused_paged_decode_attention).
        self._view_decode = self._paged and \
            not attn_impl.startswith("pallas")
        # block-table upload coalescing: the device copy is invalidated
        # only when a host-side write actually changes self._bt, so
        # steady-state segments reuse the resident array
        self._bt_dev = None
        # idle staging ring reuse: when nothing is staged the ring args
        # are all-zero / all-sentinel constants — upload them once
        self._ring0 = None
        # Per-leaf empty-state rows (batch axis moved to front, batch=1):
        # the slot-reset constant for chunked admission and the fused
        # loop's in-segment refill. Sequence-carrying leaves never need a
        # reset (their positions are rewritten before any masked read), so
        # they get a dummy scalar the reset paths skip by seq axis.
        if model.empty_state is not None:
            empty1 = model.empty_state(1, max_len, enc_len=max_len)
        else:
            s1 = model.cache_shapes(1, max_len, enc_len=max_len)
            empty1 = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), s1)
        self._reset_rows = jax.tree.map(
            lambda e, bax, sax: (jnp.moveaxis(jnp.asarray(e), bax, 0)
                                 if sax == -1 else jnp.zeros((), e.dtype)),
            empty1, self._batch_axes, self._seq_axes)
        self._tok = jnp.zeros((max_batch, 1), jnp.int32)
        self._pos = jnp.zeros((max_batch,), jnp.int32)
        self._rem = jnp.zeros((max_batch,), jnp.int32)
        # chunked-prefill staging: per-slot prompt buffer + prompt length
        # (0 = slot admitted via prefill, nothing left to feed)
        self._plen = jnp.zeros((max_batch,), jnp.int32)
        self._pbuf = jnp.zeros((max_batch, max_len), jnp.int32)
        self._prefill_fns: Dict[int, Any] = {}
        self._decode_fn = None
        self._chunk_fn = None
        self._cow_fn = None
        # open-loop state: persists across submit()/step() calls so
        # requests can arrive while earlier ones are mid-decode
        self._pending: deque = deque()
        self._slot_req: List[Optional[Request]] = [None] * max_batch
        self._gen: Dict[int, List[int]] = {}
        self._free: List[int] = list(range(max_batch))[::-1]
        self._slot_pos = np.zeros((max_batch,), np.int64)
        # host mirror of each slot's seated token row (prompt, or replay
        # prefix) plus how many of the slot's _gen entries were already
        # inside that row at seat time (preemption re-credits): the full
        # fed-token sequence of a slot is always
        # ``_slot_prefix[s] + _gen[s][_seat_credit[s]:]``, which is what
        # the spec round's draft resync replays
        self._slot_prefix: List[Optional[np.ndarray]] = [None] * max_batch
        self._seat_credit = np.zeros((max_batch,), np.int64)
        self._completed: List[Request] = []
        # staging ring (in-segment admission): FIFO of
        # (request, allocator ticket, block-table row) awaiting a freed
        # slot inside a fused segment; mirrors the device ring each step
        self._staged: deque = deque()
        self._stage_seq = 0
        # preempted requests parked host-side (``_Parked``), FIFO; they
        # re-admit ahead of pending work via the chunked-prefill seat
        self._preempted: deque = deque()
        # EWMA of per-decode-step wall time: the slack policy's estimate
        # of a request's remaining service time (positions left x this)
        self._step_est = 0.0

    @property
    def step_seconds(self) -> float:
        """Wall seconds of one decode step, dispatch to host sync (the
        EWMA the slack policy reads); 0.0 before the first step."""
        return self._step_est

    def _pool_shape(self, dims: Tuple[int, ...], bax: int, sax: int):
        """Contiguous leaf shape -> shared-pool shape: drop the batch axis,
        split the sequence axis into (n_pages, page_size). State leaves
        (sax == -1) keep their slot-indexed shape."""
        if sax == -1:
            return dims
        assert bax < sax, (dims, bax, sax)
        return (dims[:bax] + dims[bax + 1:sax]
                + (self._pool_pages, self.page_size) + dims[sax + 1:])

    def _n_positions(self, r: Request) -> int:
        """KV positions a request writes over its lifetime: the prompt plus
        one per generated token except the last (never fed back)."""
        return len(r.prompt) + max(r.max_new_tokens, 1) - 1

    # ------------------------------------------------------------------
    # compiled programs (keyed on (bucket_batch, bucket_len) shape)
    def _get_prefill(self, bucket: int, nbatch: int):
        key = (nbatch, bucket)
        fn = self._prefill_fns.get(key)
        if fn is not None:
            return fn
        model, cfg = self.model, self.model.cfg
        baxes, saxes = self._batch_axes, self._seq_axes
        paged, ps = self._paged, self.page_size

        def prefill_admit(params, cache, tok, pos, rem, plen, tokens,
                          lengths, slots, max_news, page_rows=None):
            # tokens: (nbatch, bucket); lengths/slots/max_news: (nbatch,).
            # Padding rows carry slot == max_batch: out-of-bounds scatter
            # indices are dropped, so they touch no live slot. In paged
            # mode page_rows (nbatch, ceil(bucket/ps)) routes each leaf's
            # cache slice into the slot's pages (sentinel rows drop —
            # bucket padding past the allocated pages never lands).
            self.stats["prefill_traces"] += 1   # Python side effect: runs
            batch = {"tokens": tokens,          # once per (re)trace only
                     "length": lengths}
            if cfg.family == "audio":
                batch["frames"] = jnp.zeros((nbatch, bucket, cfg.d_model),
                                            cfg.dtype)
            if cfg.family == "vlm":
                batch["image_embeds"] = jnp.zeros(
                    (nbatch, cfg.n_image_tokens, cfg.d_model), cfg.dtype)
            logits, pcache = model.prefill(params, batch)
            firsts = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)

            def insert(slot_leaf, new_leaf, bax):
                pads = [(0, 0) if i == bax else (0, t - s)
                        for i, (s, t) in enumerate(zip(new_leaf.shape,
                                                       slot_leaf.shape))]
                new_leaf = jnp.pad(new_leaf, pads).astype(slot_leaf.dtype)
                arr = jnp.moveaxis(slot_leaf, bax, 0)
                rows = jnp.moveaxis(new_leaf, bax, 0)
                arr = arr.at[slots].set(rows, mode="drop")
                return jnp.moveaxis(arr, 0, bax)

            def insert_paged(pool_leaf, new_leaf, bax, sax):
                # page-shape the slice: split its sequence axis into
                # (n_pages_of_bucket, page_size) rows, then scatter each
                # row to its block-table page (shared pool, batch-free)
                if sax == -1:
                    return insert(pool_leaf, new_leaf, bax)
                n_rows = page_rows.shape[1]
                new = jnp.moveaxis(new_leaf, bax, 0)    # (nb, .., S@sax, ..)
                padspec = [(0, 0)] * new.ndim
                padspec[sax] = (0, n_rows * ps - new.shape[sax])
                new = jnp.pad(new, padspec)
                new = new.reshape(new.shape[:sax] + (n_rows, ps)
                                  + new.shape[sax + 1:])
                new = jnp.moveaxis(new, sax, 1)         # (nb, P_b, .., ps, ..)
                new = new.reshape((nbatch * n_rows,) + new.shape[2:])
                pool = jnp.moveaxis(pool_leaf, sax - 1, 0)
                pool = pool.at[page_rows.reshape(-1)].set(
                    new.astype(pool.dtype), mode="drop")
                return jnp.moveaxis(pool, 0, sax - 1)

            if paged:
                cache = jax.tree.map(insert_paged, cache, pcache,
                                     baxes, saxes)
            else:
                cache = jax.tree.map(insert, cache, pcache, baxes)
            tok = tok.at[slots].set(firsts[:, None], mode="drop")
            pos = pos.at[slots].set(lengths, mode="drop")
            rem = rem.at[slots].set(max_news - 1, mode="drop")
            plen = plen.at[slots].set(jnp.zeros_like(max_news), mode="drop")
            return cache, tok, pos, rem, plen, firsts

        fn = jax.jit(prefill_admit)
        self._prefill_fns[key] = fn
        return fn

    def _get_chunk_admit(self):
        """Compiled chunked admission: stage the full prompt in the slot's
        device prompt buffer (no prefill dispatch) and reset the slot's
        recurrent state rows; the decode segment teacher-forces the prompt
        from there, ``decode_block`` tokens per segment."""
        if self._chunk_fn is not None:
            return self._chunk_fn
        baxes, saxes = self._batch_axes, self._seq_axes
        reset_rows = self._reset_rows
        max_len = self.max_len

        n_slots = self.max_batch

        def chunk_admit(cache, tok, pos, rem, plen, pbuf, slot, row,
                        plen_v, max_new, start):
            # slot/plen_v/max_new/start: (1,); row: (1, max_len). start
            # is the first position the seat actually feeds: 0 for plain
            # chunked admission and preemption replay, the first uncached
            # token for a prefix-cache hit (the covered prefix's KV is
            # already resident in the slot's attached pages).
            self.stats["chunk_traces"] += 1
            # KV leaves need no reset: a position is always rewritten by
            # this slot before any masked read can include it. O(1) state
            # leaves carry the previous occupant's final state and must
            # restart from the family's empty state (zeros, except e.g.
            # xLSTM's -inf stabilizers) — same primitive the fused loop's
            # in-segment refill uses, with a one-hot slot mask.
            take = jnp.arange(n_slots) == slot[0]
            cache = jax.tree.map(
                lambda leaf, bax, sax, empty_row:
                    leaf if sax != -1
                    else KV.reset_slot_rows(leaf, bax, take, empty_row),
                cache, baxes, saxes, reset_rows)
            first = jnp.take_along_axis(
                row, jnp.clip(start, 0, max_len - 1)[:, None], axis=1)
            tok = tok.at[slot].set(first)
            pos = pos.at[slot].set(start)
            rem = rem.at[slot].set(max_new)
            plen = plen.at[slot].set(plen_v)
            pbuf = pbuf.at[slot].set(row)
            return cache, tok, pos, rem, plen, pbuf

        self._chunk_fn = jax.jit(chunk_admit)
        return self._chunk_fn

    def _get_decode(self):
        if self._decode_fn is not None:
            return self._decode_fn
        model, steps, slots = self.model, self.decode_block, self.max_batch
        paged, max_len = self._paged, self.max_len
        view = self._view_decode
        R = max(self.stage_slots, 1)      # device ring capacity (static)
        max_comps = slots + R             # completion-log capacity
        baxes, saxes = self._batch_axes, self._seq_axes
        reset_rows = self._reset_rows

        def decode_segment(params, cache, tok, pos, rem, plen, pbuf,
                           ring_tok, ring_plen, ring_new, n_stage,
                           bt=None, ring_bt=None):
            # ring_tok: (R, max_len) staged prompt rows; ring_plen /
            # ring_new: (R,) prompt lengths and max_new budgets; n_stage:
            # scalar count of valid ring entries (0 disables refill);
            # ring_bt: (R, pages_per_slot) pre-reserved block-table rows.
            self.stats["decode_traces"] += 1
            slot_ids = jnp.arange(slots, dtype=jnp.int32)
            pool = cache
            if view:
                # Segment-resident views (XLA attention): gather each
                # slot's contiguous KV view from the page pool once, run
                # the *contiguous* decode program over it for the whole
                # segment, and scatter only the written span [entry pos,
                # exit pos) back through the (final) block table at exit.
                # Per-step paged indirection — a pool gather plus a pool
                # scatter per layer per token — disappears from the loop
                # body entirely, which is what closes the paged tok/s
                # gap; the in-loop math is bit-identical to the
                # contiguous engine because it *is* the same program on
                # the same shapes.
                bt0 = jnp.asarray(bt)
                cache = jax.tree.map(
                    lambda leaf, bax, sax: leaf if sax == -1
                    else KV.gather_pool_view(leaf, bt0, bax, sax),
                    pool, baxes, saxes)

            def cond(st):
                return (st["i"] < steps) & jnp.any(st["rem"] > 0)

            def body(st):
                i, cache = st["i"], st["cache"]
                tok, pos, rem = st["tok"], st["pos"], st["rem"]
                plen, pbuf = st["plen"], st["pbuf"]
                bt_c = st.get("bt")
                active = rem > 0
                dcache = dict(cache, bt=bt_c) if (paged and not view) \
                    else cache
                logits, dcache = model.decode(params, dcache, tok, pos)
                if paged and not view:
                    dcache = {k: v for k, v in dcache.items() if k != "bt"}
                cache = dcache
                nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
                # chunked prefill: while prompt tokens remain, feed the
                # next one instead of the sampled token and emit nothing
                feeding = (pos + 1) < plen
                pnext = jnp.take_along_axis(
                    pbuf, jnp.clip(pos + 1, 0, max_len - 1)[:, None],
                    axis=1)[:, 0]
                nxt = jnp.where(feeding, pnext, nxt)
                emit = jnp.where(active & ~feeding, nxt, -1)
                out = lax.dynamic_update_slice(st["out"], emit[:, None],
                                               (0, i))
                tok = jnp.where(active[:, None], nxt[:, None], tok)
                pos = jnp.where(active, pos + 1, pos)
                rem = jnp.where(active & ~feeding, rem - 1, rem)
                # ---- completion log + in-segment slot refill ----------
                # Freshly finished slots are logged (slot, step) in slot
                # order; the first `avail` of them pull the next staged
                # requests (FIFO: j-th admitted completion of the segment
                # takes ring entry j), resetting the slot inside the loop
                # so the dispatch retires multiple requests per slot.
                fin = active & ~feeding & (rem == 0)
                nfin = jnp.sum(fin.astype(jnp.int32))
                head = st["head"]
                avail = n_stage - head
                rank = jnp.cumsum(fin.astype(jnp.int32)) - 1
                adm = fin & (rank < avail)
                src = jnp.clip(head + rank, 0, R - 1)
                log_idx = jnp.where(fin, st["n_comp"] + rank, max_comps)
                comp_slot = st["comp_slot"].at[log_idx].set(
                    slot_ids, mode="drop")
                comp_step = st["comp_step"].at[log_idx].set(i, mode="drop")
                comp_adm = st["comp_adm"].at[log_idx].set(
                    adm.astype(jnp.int32), mode="drop")
                rows = jnp.take(ring_tok, src, axis=0)     # (B, max_len)
                tok = jnp.where(adm[:, None], rows[:, :1], tok)
                pbuf = jnp.where(adm[:, None], rows, pbuf)
                pos = jnp.where(adm, 0, pos)
                rem = jnp.where(adm, jnp.take(ring_new, src), rem)
                plen = jnp.where(adm, jnp.take(ring_plen, src), plen)
                cache = jax.tree.map(
                    lambda leaf, bax, sax, row:
                        leaf if sax != -1
                        else KV.reset_slot_rows(leaf, bax, adm, row),
                    cache, baxes, saxes, reset_rows)
                new = dict(
                    i=i + 1, cache=cache, tok=tok, pos=pos, rem=rem,
                    plen=plen, pbuf=pbuf, out=out,
                    head=head + jnp.minimum(nfin, jnp.maximum(avail, 0)),
                    comp_slot=comp_slot, comp_step=comp_step,
                    comp_adm=comp_adm, n_comp=st["n_comp"] + nfin,
                    busy=st["busy"] + jnp.sum(active.astype(jnp.int32)))
                if paged:
                    new["bt"] = jnp.where(adm[:, None],
                                          jnp.take(ring_bt, src, axis=0),
                                          bt_c)
                if view:
                    # a refilled slot restarts at position 0: its whole
                    # written span flushes through the ring's block-table
                    # row at exit (the previous occupant's in-view tail
                    # is never written back — its pages are released at
                    # harvest and may already be re-handed)
                    new["seg"] = jnp.where(adm, 0, st["seg"])
                return new

            st0 = dict(i=jnp.int32(0), cache=cache, tok=tok, pos=pos,
                       rem=rem, plen=plen, pbuf=pbuf,
                       out=jnp.full((slots, steps), -1, jnp.int32),
                       head=jnp.int32(0),
                       comp_slot=jnp.zeros((max_comps,), jnp.int32),
                       comp_step=jnp.zeros((max_comps,), jnp.int32),
                       comp_adm=jnp.zeros((max_comps,), jnp.int32),
                       n_comp=jnp.int32(0), busy=jnp.int32(0))
            if paged:
                st0["bt"] = jnp.asarray(bt)
            if view:
                st0["seg"] = pos
            st = lax.while_loop(cond, body, st0)
            out_cache = st["cache"]
            if view:
                # flush each slot's written span back to the page pool
                # through its *final* block table (in-segment refills
                # switched rows mid-loop); sentinel rows drop, so
                # preempted/idle slots touch nothing
                out_cache = jax.tree.map(
                    lambda pool_leaf, view_leaf, bax, sax:
                        view_leaf if sax == -1
                        else KV.scatter_pool_view(
                            pool_leaf, view_leaf, st["bt"], bax, sax,
                            st["seg"], st["pos"]),
                    pool, out_cache, baxes, saxes)
            return (out_cache, st["tok"], st["pos"], st["rem"],
                    st["plen"], st["pbuf"], st["out"], st["comp_slot"],
                    st["comp_step"], st["comp_adm"], st["n_comp"],
                    st["busy"], st["i"])

        if paged:
            self._decode_fn = jax.jit(decode_segment)
        else:
            self._decode_fn = jax.jit(
                lambda params, cache, tok, pos, rem, plen, pbuf,
                rtok, rplen, rnew, n_stage:
                decode_segment(params, cache, tok, pos, rem, plen, pbuf,
                               rtok, rplen, rnew, n_stage))
        return self._decode_fn

    def _get_verify(self):
        """Compiled target-side verification of one speculative window.

        Feeds ``toks`` (B, k+1) — the slot's pending token followed by the
        draft's k proposals — teacher-forced at positions ``pos..pos+k``,
        and returns the target's greedy argmax at every window position.
        Dense verifies all k+1 positions in one batched ``decode_window``
        pass; families without an exact batched window (hybrid/ssm, whose
        recurrent state is sequential by nature) run a jitted
        ``fori_loop`` of single-token ``decode`` — still one dispatch and
        one host sync per round, just k+1 sequential cells inside it.
        """
        if self._verify_fn is not None:
            return self._verify_fn
        model = self.model
        paged = self._paged
        S = self.spec_k + 1

        if model.decode_window is not None:
            def verify(params, cache, toks, pos, bt=None):
                self.stats["verify_traces"] += 1
                vc = dict(cache, bt=bt) if paged else cache
                logits, vc = model.decode_window(params, vc, toks, pos)
                if paged:
                    vc = {k: v for k, v in vc.items() if k != "bt"}
                preds = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return vc, preds
        else:
            def verify(params, cache, toks, pos, bt=None):
                self.stats["verify_traces"] += 1
                vc = dict(cache, bt=bt) if paged else cache
                preds0 = jnp.zeros((toks.shape[0], S), jnp.int32)

                def body(i, st):
                    vc, preds = st
                    tk = lax.dynamic_slice(
                        toks, (0, i), (toks.shape[0], 1))
                    logits, vc = model.decode(params, vc, tk, pos + i)
                    nxt = jnp.argmax(logits[:, -1, :], axis=-1) \
                        .astype(jnp.int32)
                    preds = lax.dynamic_update_slice(
                        preds, nxt[:, None], (0, i))
                    return (vc, preds)

                vc, preds = lax.fori_loop(0, S, body, (vc, preds0))
                if paged:
                    vc = {k: v for k, v in vc.items() if k != "bt"}
                return vc, preds

        self._verify_fn = jax.jit(verify)
        return self._verify_fn

    # ------------------------------------------------------------------
    def warmup(self, prompt_lens: Sequence[int] = (),
               include_decode: bool = True) -> None:
        """Compile prefill executables for the (batch, length) buckets
        covering ``prompt_lens`` (plus the minimum bucket) and the decode
        segment.

        Warmup calls run against the live state with every scatter index
        out of bounds (dropped), so engine state is untouched; subsequent
        serving on these buckets never recompiles.
        """
        lens = [n for n in prompt_lens
                if self.chunk_threshold is None or n <= self.chunk_threshold]
        buckets = {bucket_len(max(n, 1), self.min_bucket, self.max_len)
                   for n in lens + [1]}       # chunked lens never prefill
        nbatches = {1, self.max_batch} if self._group_admit else {1}
        for b in sorted(buckets):
            for nb in sorted(nbatches):
                if (nb, b) in self._prefill_fns:
                    continue        # already compiled; skip the dummy run
                fn = self._get_prefill(b, nb)
                args = [self.params, self._cache, self._tok, self._pos,
                        self._rem, self._plen, np.zeros((nb, b), np.int32),
                        np.ones((nb,), np.int32),
                        np.full((nb,), self.max_batch, np.int32),
                        np.ones((nb,), np.int32)]
                if self._paged:
                    args.append(np.full((nb, self._page_rows_for(b)),
                                        self.n_pages, np.int32))
                out = fn(*args)
                jax.block_until_ready(out[-1])
        if include_decode and self._decode_fn is None:
            fn = self._get_decode()
            R = max(self.stage_slots, 1)
            args = [self.params, self._cache, self._tok, self._pos,
                    jnp.zeros((self.max_batch,), jnp.int32), self._plen,
                    self._pbuf, np.zeros((R, self.max_len), np.int32),
                    np.zeros((R,), np.int32), np.zeros((R,), np.int32),
                    np.int32(0)]
            if self._paged:
                args += [self._bt, KV.sentinel_block_table(
                    R, self.pages_per_slot, self.n_pages)]
            out = fn(*args)
            jax.block_until_ready(out[-1])
        if include_decode and self._spec is not None and \
                self._verify_fn is None:
            # speculative programs: the verify window and the draft's
            # k-step propose. The verify output cache is discarded (the
            # engine's cache is functional and never reassigned here) so
            # live state is untouched; the draft cache does take garbage
            # writes, which the all-stale sync markers make harmless.
            verify = self._get_verify()
            toks0 = jnp.zeros((self.max_batch, self.spec_k + 1),
                              jnp.int32)
            if self._paged:
                if self._bt_dev is None:
                    self._bt_dev = jnp.asarray(self._bt)
                vout = verify(self.params, self._cache, toks0,
                              self._pos, self._bt_dev)
            else:
                vout = verify(self.params, self._cache, toks0, self._pos)
            jax.block_until_ready(vout[1])
            drafts = self._spec.propose(
                jnp.zeros((self.max_batch, 1), jnp.int32),
                jnp.zeros((self.max_batch,), jnp.int32))
            jax.block_until_ready(drafts)
            self._spec.state = [None] * self.max_batch
        if (self.chunk_threshold is not None
                or self.admission == "optimistic"
                or self._prefix is not None) and \
                self._chunk_fn is None:
            # optimistic engines seat preempted prefixes through the chunk
            # path even with chunking off, and prefix-cache hits seat
            # through it too: compile it out of band in both cases
            fn = self._get_chunk_admit()
            out = fn(self._cache, self._tok, self._pos, self._rem,
                     self._plen, self._pbuf,
                     np.full((1,), self.max_batch, np.int32),
                     np.zeros((1, self.max_len), np.int32),
                     np.zeros((1,), np.int32), np.zeros((1,), np.int32),
                     np.zeros((1,), np.int32))
            jax.block_until_ready(out[1])

    def _page_rows_for(self, bucket: int) -> int:
        """Block-table rows a bucket-wide prefill slice spans."""
        return -(-bucket // self.page_size)

    # ------------------------------------------------------------------
    def _admit_group(self, bucket: int, rs: List[Request],
                     slots: List[int]) -> np.ndarray:
        """One prefill dispatch admitting same-bucket requests into slots.

        Admit batches are bucketed to {1, max_batch} so the executable
        count stays at <= 2 per prompt bucket; padding rows point their
        scatter index past the last slot and are dropped. In paged mode
        each request's prompt pages are allocated here (its block-table
        row was reserved at pop time) and the prefill scatters page-shaped
        cache slices through them.
        """
        m = len(rs)
        nb = 1 if m == 1 else self.max_batch
        tokens = np.zeros((nb, bucket), np.int32)
        lengths = np.ones((nb,), np.int32)
        slot_idx = np.full((nb,), self.max_batch, np.int32)
        max_news = np.ones((nb,), np.int32)
        for j, (r, s) in enumerate(zip(rs, slots)):
            tokens[j, : len(r.prompt)] = r.prompt       # right-pad
            lengths[j] = len(r.prompt)
            slot_idx[j] = s
            max_news[j] = max(r.max_new_tokens, 1)
        fn = self._get_prefill(bucket, nb)
        args = [self.params, self._cache, self._tok, self._pos, self._rem,
                self._plen, tokens, lengths, slot_idx, max_news]
        if self._paged:
            n_rows = self._page_rows_for(bucket)
            page_rows = np.full((nb, n_rows), self.n_pages, np.int32)
            for j, (r, s) in enumerate(zip(rs, slots)):
                self._grow_slot(s, len(r.prompt))
                page_rows[j] = self._bt[s, :n_rows]
            args.append(page_rows)
        (self._cache, self._tok, self._pos, self._rem, self._plen,
         firsts) = fn(*args)
        self.stats["prefill_dispatches"] += 1
        self.stats["prefill_prompt_tokens"] += sum(len(r.prompt) for r in rs)
        self.stats["prefill_computed_tokens"] += nb * bucket
        self.stats["admitted"] += m
        for r, s in zip(rs, slots):
            self._slot_prefix[s] = np.asarray(r.prompt, np.int32)
            self._seat_credit[s] = 0
        if self._prefix is not None:
            for r, s in zip(rs, slots):
                self._seat_digests[s] = self._prefix.chain(r.prompt)
                self._reg_upto[s] = 0
        return np.asarray(firsts)[:m]

    def _lookup_attach(self, slot: int,
                       tokens: np.ndarray) -> Optional[int]:
        """Prefix-cache lookup for a request about to be seated in
        ``slot`` (which already holds its reservation): attach the hit
        pages to the slot's block table (refcounted) and return the
        teacher-forcing start position — the first uncached token — or
        ``None`` on a miss.

        When the hit covers every full page of the prompt, the seat
        still rewrites position ``plen - 1`` (its logits produce the
        first output token), which lands *inside* the last shared page:
        that page is copy-on-write duplicated first — unless this slot
        is its only holder, in which case it is written in place and
        unindexed until the rewrite lands (no sharer can appear mid-
        flight, keeping "no write to a page with refcount > 1" exact).
        """
        if self._prefix is None:
            return None
        hit = self._prefix.lookup(tokens)
        if not hit:
            return None
        ps = self.page_size
        plen = len(tokens)
        self._alloc.attach(slot, hit)
        self._bt[slot, :len(hit)] = hit
        self._bt_dev = None
        start = min(len(hit) * ps, plen - 1)
        if len(hit) * ps >= plen:
            if self._alloc.refcount(hit[-1]) > 1:
                old, new = self._alloc.cow(slot, len(hit) - 1)
                self._bt[slot, len(hit) - 1] = new
                self._copy_page(old, new)
                self.stats["cow_copies"] += 1
            else:
                self._prefix.unindex(hit[-1])
        self.stats["prefix_hits"] += 1
        self.stats["prefix_pages_reused"] += len(hit)
        self.stats["prefix_tokens_skipped"] += start
        return start

    def _grow_slot(self, slot: int, n_positions: int) -> None:
        """Extend ``slot``'s block table to cover positions [0, n)."""
        held = len(self._alloc.pages_of(slot))
        new = self._alloc.cover(slot, n_positions)
        if new:
            self._bt[slot, held:held + len(new)] = new
            self._bt_dev = None

    def _copy_page(self, src: int, dst: int) -> None:
        """Device half of copy-on-write: duplicate physical page ``src``
        into ``dst`` across every paged cache leaf (one jitted scatter,
        page ids traced so all copies share the executable)."""
        if self._cow_fn is None:
            saxes = self._seq_axes

            def cow_copy(cache, s, d):
                return jax.tree.map(
                    lambda leaf, sax: leaf if sax == -1
                    else KV.copy_pool_page(leaf, s, d, sax),
                    cache, saxes)

            self._cow_fn = jax.jit(cow_copy)
        self._cache = self._cow_fn(self._cache, np.int32(src),
                                   np.int32(dst))

    def _seat_prefix(self, slot: int, prefix: np.ndarray,
                     max_new: int, start: int = 0) -> None:
        """Seat a token prefix in ``slot`` for teacher-forced replay: the
        prefix goes to the slot's device prompt buffer, the slot's state
        rows reset to the family's empty state, and the next segments feed
        it ``decode_block`` tokens per dispatch before emitting ``max_new``
        greedy tokens. The primitive under chunked admission (prefix ==
        prompt) and preemption recovery (prefix == prompt + tokens already
        generated, which makes the continuation bit-identical). A
        prefix-cache hit passes ``start`` > 0: positions [0, start) are
        already resident in the slot's attached pages, so the feed starts
        at the first uncached token."""
        plen = len(prefix)
        row = np.zeros((1, self.max_len), np.int32)
        row[0, :plen] = prefix
        fn = self._get_chunk_admit()
        (self._cache, self._tok, self._pos, self._rem, self._plen,
         self._pbuf) = fn(
            self._cache, self._tok, self._pos, self._rem, self._plen,
            self._pbuf, np.asarray([slot], np.int32), row,
            np.asarray([plen], np.int32),
            np.asarray([max(max_new, 1)], np.int32),
            np.asarray([start], np.int32))
        self._slot_prefix[slot] = np.asarray(prefix, np.int32)
        self._seat_credit[slot] = 0
        if self._prefix is not None:
            self._seat_digests[slot] = self._prefix.chain(prefix)
            self._reg_upto[slot] = 0

    def _chunk_seat(self, r: Request, slot: int) -> None:
        """Stage ``r``'s prompt in ``slot``'s device prompt buffer and
        reset the slot's state rows (no prefill dispatch): shared by
        chunked admission and the boundary fallback that seats staged
        requests into freed slots."""
        self._seat_prefix(slot, np.asarray(r.prompt, np.int32),
                          max(r.max_new_tokens, 1))

    def _admit_chunk(self, r: Request, slot: int) -> None:
        """Chunked admission: no prefill dispatch — stage the prompt in
        the slot's device prompt buffer; the next decode segments feed it
        ``decode_block`` tokens at a time."""
        self._chunk_seat(r, slot)
        self.stats["chunk_admits"] += 1
        self.stats["admitted"] += 1

    # ------------------------------------------------------------------
    # open-loop core: submit / step / drain_completions
    @property
    def busy(self) -> bool:
        """True while any request is pending admission, staged for
        in-segment admission, parked after a preemption, or mid-decode."""
        return bool(self._pending) or bool(self._staged) or \
            bool(self._preempted) or \
            any(r is not None for r in self._slot_req)

    def _validate(self, r: Request) -> None:
        if len(r.prompt) + r.max_new_tokens > self.max_len:
            raise ValueError(
                f"request {r.rid}: prompt_len {len(r.prompt)} + max_new "
                f"{r.max_new_tokens} exceeds engine max_len "
                f"{self.max_len}")
        if self._alloc is not None:
            need = self._alloc.pages_needed(self._n_positions(r))
            if need > self.n_pages:
                raise ValueError(
                    f"request {r.rid}: needs {need} pages but the pool "
                    f"holds {self.n_pages}; it could never be admitted")

    def submit(self, r: Request) -> None:
        """Enqueue a request; may be called at any time, including while
        other requests are mid-decode (it joins at the next ``step()``).
        The latency clock starts at ``r.arrival`` (stamped now if unset)."""
        self._validate(r)
        if r.arrival == 0.0:
            r.arrival = time.perf_counter()
        self._pending.append(r)

    def _admit_pending(self) -> None:
        """Fill free slots from the pending queue (grouped by bucket),
        then top up the staging ring for in-segment admission.

        In paged mode admission is additionally gated on free pages: the
        queue head must fit its worst-case page reservation before it (or
        anything behind it — FIFO) is admitted or staged. Prompts longer
        than ``chunk_threshold`` take the chunked path; the rest prefill.
        Staged requests hold their worst-case reservation from staging
        time under a per-request ticket, with their first ``decode_block``
        positions' pages materialized up front — the fused segment that
        pulls them in has no host boundary at which to grow them."""
        now = time.perf_counter()
        # parked (preempted) requests re-admit ahead of everything else:
        # they are the oldest admitted work, they hold zero pages while
        # parked, and seating them first bounds how often the same request
        # gets re-preempted. Recovery teacher-forces the full prefix
        # (prompt + tokens already generated) through the chunked-prefill
        # seat, so the continuation is bit-identical to an uninterrupted
        # run; tokens generated before the preempt are re-credited to the
        # slot's emission list rather than regenerated.
        #
        # Re-admission is deliberately NOT optimistic: it waits until the
        # request's full remaining worst case sits in actually-free pages.
        # Optimistically re-admitting into the still-contended pool that
        # just evicted it is ping-pong — every bounce replays the whole
        # prefix (pure waste) before any new token lands. The hysteresis
        # costs nothing at the peak (initial admits already filled every
        # slot) and converts preempt-thrash into one park per victim.
        while self._preempted and self._free:
            p = self._preempted[0]
            npos = self._n_positions(p.req)
            if self._alloc is not None:
                if self.admission == "optimistic":
                    if self._alloc.pages_needed(npos) > self._alloc.n_avail:
                        break
                elif not self._alloc.can_reserve(npos):
                    break
            self._preempted.popleft()
            slot = self._free.pop()
            if p.kv is not None:
                # host-RAM swap-in: fresh pages + the scattered payload
                # replace the teacher-forced replay entirely, so recovery
                # costs zero decode compute
                self._alloc.reserve(slot, npos,
                                    strict=self.admission != "optimistic")
                self._swap_in(slot, p)
                continue
            start = None
            if self._alloc is not None:
                self._alloc.reserve(slot, npos,
                                    strict=self.admission != "optimistic")
                # the victim's registered prompt pages went to the cached
                # pool when it was preempted, so re-admission usually
                # re-hits the cache and replays only the uncached tail
                start = self._lookup_attach(slot, p.prefix)
                if self.admission == "optimistic":
                    # materialize the first stride now so this pass's
                    # free-page accounting stays exact for the next seat
                    self._grow_slot(slot, min(npos, (start or 0)
                                              + self.decode_block))
            self._seat_prefix(slot, p.prefix,
                              max(p.req.max_new_tokens - len(p.done), 1),
                              start=start or 0)
            self.stats["preempt_readmits"] += 1
            self._gen[slot] = list(p.done)
            # the seated prefix already contains the re-credited tokens
            self._seat_credit[slot] = len(p.done)
            self._slot_req[slot] = p.req
            self._slot_pos[slot] = start or 0
        # boundary fallback: seat already-staged requests into free slots
        # the loop never refilled — a slot can come back without an
        # in-loop admission (e.g. a max_new==1 prefill finishes at
        # admission and is swept at harvest), and the staged FIFO precedes
        # everything still in pending. A staged request at a boundary IS a
        # chunk admission whose pages are already reserved.
        while self._staged and self._free:
            r, ticket, bt_row = self._staged.popleft()
            slot = self._free.pop()
            if self._alloc is not None:
                self._alloc.rekey(ticket, slot)
                self._bt[slot, :] = bt_row
                self._bt_dev = None
            r.admitted = now
            self._chunk_seat(r, slot)
            self.stats["admitted"] += 1
            self._gen[slot] = []
            self._slot_req[slot] = r
            self._slot_pos[slot] = 0
        prefills: List[Tuple[Request, int]] = []
        # no new admissions while preempted work waits: a fresh request
        # seated now would take the very pages the parked request is
        # waiting to re-earn (arrival-order inversion + another preempt
        # cycle). The parked queue drains first, always — its head fits
        # the pool by the submit()-time validation.
        while self._pending and self._free and not self._preempted:
            r = self._pending[0]
            npos = self._n_positions(r)
            chunked = self.chunk_threshold is not None and \
                len(r.prompt) > self.chunk_threshold
            if self._alloc is not None:
                if self.admission == "optimistic":
                    # expected usage: a prefill needs its prompt pages at
                    # the dispatch; a chunked prompt only its first
                    # decode_block stride; a prefix-cache hit only the
                    # stride past its cached pages (estimated here, +1
                    # for a possible copy-on-write page). The decode tail
                    # grows lazily — under pressure the grow path
                    # preempts, never wedges.
                    hit_est = len(self._prefix.lookup(r.prompt)) \
                        if self._prefix is not None else 0
                    if hit_est:
                        first = min(npos, hit_est * self.page_size
                                    + self.decode_block)
                        need = self._alloc.pages_needed(first) \
                            - hit_est + 1
                    else:
                        first = min(npos, self.decode_block) if chunked \
                            else len(r.prompt)
                        need = self._alloc.pages_needed(first)
                    if need > self._alloc.n_avail:
                        break
                elif not self._alloc.can_reserve(npos):
                    break
            self._pending.popleft()
            slot = self._free.pop()
            start = None
            if self._alloc is not None:
                self._alloc.reserve(slot, npos,
                                    strict=self.admission != "optimistic")
                start = self._lookup_attach(slot, r.prompt)
                if self.admission == "optimistic":
                    # cover the expected pages now so this pass's free-page
                    # accounting stays exact for the next queue head
                    if start is not None:
                        first = min(npos, start + self.decode_block)
                    self._grow_slot(slot, first)
            r.admitted = now
            if start is not None:
                # cache hit: the covered prefill is skipped entirely —
                # the seat teacher-forces from the first uncached token
                self._seat_prefix(slot, np.asarray(r.prompt, np.int32),
                                  max(r.max_new_tokens, 1), start=start)
                self.stats["admitted"] += 1
                self._gen[slot] = []        # first token comes via emit
                self._slot_req[slot] = r
                self._slot_pos[slot] = start
            elif chunked:
                self._admit_chunk(r, slot)
                self._gen[slot] = []        # first token comes via emit
                self._slot_req[slot] = r
                self._slot_pos[slot] = 0
            else:
                prefills.append((r, slot))
        groups: Dict[int, List[Tuple[Request, int]]] = {}
        for r, s in prefills:
            b = bucket_len(len(r.prompt), self.min_bucket, self.max_len)
            groups.setdefault(b, []).append((r, s))
        for b, pairs in sorted(groups.items()):
            units = [pairs] if self._group_admit else \
                [[p] for p in pairs]
            for unit in units:
                rs = [r for r, _ in unit]
                slots = [s for _, s in unit]
                firsts = self._admit_group(b, rs, slots)
                for r, s, f in zip(rs, slots, firsts):
                    self._gen[s] = [int(f)]
                    self._slot_req[s] = r
                    self._slot_pos[s] = len(r.prompt)
        # ---- staging ring: queue overflow rides into the segment ------
        while self.stage_slots and self._pending and \
                not self._preempted and \
                len(self._staged) < self.stage_slots:
            r = self._pending[0]
            npos = self._n_positions(r)
            if self._alloc is not None:
                if self.admission == "optimistic":
                    if self._alloc.pages_needed(
                            min(npos, self.decode_block)) > \
                            self._alloc.n_avail:
                        break
                elif not self._alloc.can_reserve(npos):
                    break                   # FIFO: nothing jumps the line
            self._pending.popleft()
            ticket = ("stage", self._stage_seq)
            self._stage_seq += 1
            bt_row = None
            if self._alloc is not None:
                self._alloc.reserve(ticket, npos,
                                    strict=self.admission != "optimistic")
                pages = self._alloc.cover(
                    ticket, min(npos, self.decode_block))
                bt_row = np.full((self.pages_per_slot,), self.n_pages,
                                 np.int32)
                bt_row[:len(pages)] = pages
            self._staged.append((r, ticket, bt_row))
            self.stats["staged"] += 1

    # ------------------------------------------------------------------
    # preemption: park / pick victim / relieve pressure
    def _preempt_slot(self, v: int) -> None:
        """Preempt ``v``'s occupant: free its pages, park the request
        host-side with its prompt plus every token generated so far, and
        deactivate the slot on device. Host-boundary only (between
        dispatches)."""
        r = self._slot_req[v]
        done = self._gen.pop(v)[: r.max_new_tokens]
        prefix = np.concatenate([np.asarray(r.prompt, np.int32),
                                 np.asarray(done, np.int32)])
        r.preemptions += 1
        self.stats["preemptions"] += 1
        # host-RAM swap capture must precede release(): pages_of() is the
        # victim's block-table order, and a released page may be re-handed
        # (and rewritten) before the next host boundary
        kv, swap_bytes = (self._swap_out(v, prefix)
                          if self.swap is not None else (None, 0))
        self._slot_req[v] = None
        self._free.append(v)
        if self._alloc is not None:
            # shared pages only lose this slot's reference; the victim's
            # registered prompt pages stay indexed (cached once idle), so
            # its re-admission usually re-hits the prefix cache
            self._alloc.release(v)
            self._bt[v, :] = self.n_pages
            self._bt_dev = None
        self._preempted.append(_Parked(r, prefix.astype(np.int32),
                                       list(done), kv=kv,
                                       swap_bytes=swap_bytes))
        # rem == 0 deactivates the slot: the next fused segment neither
        # advances it, emits for it, nor logs a completion for it (and in
        # paged mode its sentinel block-table row drops any KV write)
        self._rem = jnp.asarray(self._rem).at[v].set(0)

    def _swap_out(self, v: int, prefix: np.ndarray):
        """Capture slot ``v``'s live KV pages to host RAM (swap="host").

        Returns ``(payload, nbytes)`` or ``(None, 0)`` on fallback. The
        payload is a cache-tree of host arrays, one ``(n_pages_held,
        page_size, ...)`` block per leaf in block-table order; bytes are
        *copied*, so shared/refcounted pages are safe to capture and the
        device pages reclaim immediately after.

        Gates (each miss counts ``swap_fallbacks`` and parks the victim on
        the recompute path instead):
        * paged layout with every leaf pageable (``_all_paged`` — an O(1)
          recurrent state summarizes the prefix outside the pool, so
          ssm/hybrid always recompute),
        * the victim is past its seated-prefix feed (mid-chunked-prefill
          state lives in ``_plen``/``_pbuf``, not just pages),
        * the victim still owes tokens,
        * the capture fits ``swap_budget_bytes`` alongside payloads
          already parked.
        """
        if not (self._paged and self._all_paged):
            self.stats["swap_fallbacks"] += 1
            return None, 0
        pos = int(self._slot_pos[v])
        r = self._slot_req[v]
        done_n = len(prefix) - len(r.prompt)
        # decode phase at a host boundary: fed tokens == pos, written KV
        # positions [0, pos), and prefix (prompt + done) is exactly the
        # fed tokens plus the one pending token — len(prefix) == pos + 1.
        # A victim still feeding its seated prefix, or one with no tokens
        # left to owe, recomputes instead.
        if pos + 1 != len(prefix) or done_n < 1 \
                or done_n >= r.max_new_tokens:
            self.stats["swap_fallbacks"] += 1
            return None, 0
        page_ids = self._alloc.pages_of(v)
        if not page_ids:
            self.stats["swap_fallbacks"] += 1
            return None, 0
        nbytes = 0
        for leaf, sax in zip(jax.tree.leaves(self._cache),
                             jax.tree.leaves(self._seq_axes)):
            per_page = (leaf.size // leaf.shape[sax - 1]) \
                * leaf.dtype.itemsize
            nbytes += per_page * len(page_ids)
        if self.swap_budget_bytes is not None and \
                self._swap_live_bytes + nbytes > self.swap_budget_bytes:
            self.stats["swap_fallbacks"] += 1
            return None, 0
        payload = jax.tree.map(
            lambda leaf, sax: KV.gather_pool_pages(leaf, page_ids, sax),
            self._cache, self._seq_axes)
        self._swap_live_bytes += nbytes
        self.stats["swap_out"] += 1
        self.stats["swap_bytes"] += nbytes
        return payload, nbytes

    def _swap_in(self, slot: int, p: _Parked) -> None:
        """Re-admit a swapped-out victim into ``slot`` with zero replay
        compute: allocate fresh pages, scatter the host payload back, and
        restore the decode-phase device mirrors — the continuation is
        bit-identical to an uninterrupted run because (KV, pending token,
        pos) is exactly the state the victim was parked with. The caller
        has already reserved ``slot``'s worst case."""
        prefix = p.prefix
        n_held = 0
        for leaf in jax.tree.leaves(p.kv):
            n_held = leaf.shape[0]
            break
        pages = self._alloc.cover(slot, n_held * self.page_size)
        assert len(pages) == n_held, (len(pages), n_held)
        self._bt[slot, :n_held] = pages
        self._bt_dev = None
        self._cache = jax.tree.map(
            lambda leaf, host, sax:
                KV.scatter_pool_pages(leaf, host, pages, sax),
            self._cache, p.kv, self._seq_axes)
        pos = len(prefix) - 1
        rem = p.req.max_new_tokens - len(p.done)
        self._tok = self._tok.at[slot, 0].set(int(prefix[-1]))
        self._pos = self._pos.at[slot].set(pos)
        self._rem = jnp.asarray(self._rem).at[slot].set(rem)
        self._plen = self._plen.at[slot].set(0)
        self._gen[slot] = list(p.done)
        self._seat_credit[slot] = len(p.done)
        self._slot_prefix[slot] = np.asarray(prefix, np.int32)
        self._slot_req[slot] = p.req
        self._slot_pos[slot] = pos
        # the restored pages are private copies with unknown digests:
        # skip prefix registration for this occupancy
        self._seat_digests[slot] = []
        self._reg_upto[slot] = 0
        if self._spec is not None:
            self._spec.state[slot] = None
        self._swap_live_bytes -= p.swap_bytes
        self.stats["swap_in"] += 1
        self.stats["preempt_readmits"] += 1

    def _pick_victim(self, exclude: int) -> Optional[int]:
        """Choose a live slot to preempt (never ``exclude``, the slot
        whose growth triggered the pressure). ``slack`` preempts the
        request that can best afford the round trip: most slack = deadline
        minus elapsed minus estimated remaining service (positions left x
        EWMA step time), no-SLO requests counting as infinite slack, ties
        broken toward never-yet-preempted then longest-remaining — a slot
        mid-way through replaying a preempted prefix resets its position
        counter, so without the preemption-count tie-break it *looks* like
        the longest-remaining candidate and the same request bounces
        between park and replay while fresh requests sail through. ``lru``
        preempts the most recently admitted request (vLLM-style recompute:
        the youngest has the least work to replay)."""
        cands = [s for s, r in enumerate(self._slot_req)
                 if r is not None and s != exclude]
        if not cands:
            return None
        if self.preempt_policy == "lru":
            return max(cands,
                       key=lambda s: (self._slot_req[s].admitted, s))
        now = time.perf_counter()

        def slack(s: int):
            r = self._slot_req[s]
            left = max(self._n_positions(r) - int(self._slot_pos[s]), 1)
            est = left * self._step_est
            sl = float("inf") if r.slo is None \
                else (r.arrival + r.slo) - now - est
            return (sl, -r.preemptions, left, s)

        return max(cands, key=slack)

    def _relieve_pressure(self, protect: int) -> bool:
        """Free pages under pressure, cheapest first: un-stage the newest
        staged request (zero work lost — it returns to the head of
        pending, FIFO preserved), then preempt a live victim. Returns
        False when nothing is left to free."""
        if self._staged:
            r, ticket, _bt_row = self._staged.pop()
            if self._alloc is not None:
                self._alloc.release(ticket)
            self._pending.appendleft(r)
            return True
        v = self._pick_victim(exclude=protect)
        if v is None:
            return False
        self._preempt_slot(v)
        return True

    def preempt(self, slot: int) -> None:
        """Forcibly preempt the request in ``slot`` (fault injection and
        tests; the engine preempts on its own under page pressure): park
        it and free its resources. It re-admits through the teacher-forced
        replay path with a bit-identical continuation. Call between
        ``step()`` boundaries only."""
        if not self._chunk_ok:
            raise ValueError(
                f"family {self.model.cfg.family!r} cannot recover a "
                "preempted request (no teacher-forced replay path)")
        if not 0 <= slot < self.max_batch or self._slot_req[slot] is None:
            raise ValueError(f"slot {slot} is not live")
        self._preempt_slot(slot)

    def cancel(self, slot: int) -> None:
        """Cancel the request in ``slot`` (deadline enforcement): free its
        pages and the slot immediately and complete the request with
        whatever tokens it generated — no parking, no replay. The caller
        marks the timeout verdict; the engine just stops burning compute.
        Call between ``step()`` boundaries only (like ``preempt``)."""
        if not 0 <= slot < self.max_batch or self._slot_req[slot] is None:
            raise ValueError(f"slot {slot} is not live")
        r = self._slot_req[slot]
        r.cancelled = True
        self._retire_slot(slot, r, time.perf_counter())
        self._free.append(slot)
        self._rem = jnp.asarray(self._rem).at[slot].set(0)
        if self._spec is not None:
            self._spec.state[slot] = None

    def cancel_overdue(self, now: Optional[float] = None) -> int:
        """Cancel every request whose SLO deadline has already passed:
        live slots through :meth:`cancel` (pages and the slot free
        immediately), queued and parked requests completed in place with
        whatever they have. Staged requests are left to be pulled into a
        slot and swept on a later call — they hold no decode compute yet.
        Returns the number of requests cancelled."""
        now = time.perf_counter() if now is None else now
        n = 0
        for s, r in enumerate(self._slot_req):
            if r is not None and r.slo is not None and \
                    now - r.arrival > r.slo:
                self.cancel(s)
                n += 1
        for q in (self._pending, self._preempted):
            keep: List[Any] = []
            while q:
                item = q.popleft()
                parked = isinstance(item, _Parked)
                r = item.req if parked else item
                if r.slo is None or now - r.arrival <= r.slo:
                    keep.append(item)
                    continue
                if parked:
                    # its pages were freed at preemption; only the host
                    # swap payload (if any) still charges the budget
                    self._swap_live_bytes -= item.swap_bytes
                    r.tokens = np.asarray(
                        item.done[: r.max_new_tokens], np.int32)
                else:
                    r.tokens = np.zeros((0,), np.int32)
                r.cancelled = True
                r.latency = now - r.arrival
                self._completed.append(r)
                n += 1
            q.extend(keep)
        return n

    def _flush_stream(self, slot: int, r: Request, now: float) -> None:
        """Move tokens past the request's streaming cursor into the
        partial-output buffer (no-op unless ``stream=True``). The cursor
        lives on the Request, so a preempted occupant whose generated
        tokens are re-credited at replay never re-streams them."""
        if not self.stream:
            return
        done = self._gen.get(slot)
        if done is None:
            return
        n = min(len(done), r.max_new_tokens)
        if n > r.streamed:
            if r.first_token < 0.0:
                r.first_token = now
                if spans.on():
                    spans.interval("engine.queue", spans.ns(r.arrival),
                                   spans.ns(r.admitted), r.qid)
                    spans.interval("engine.first_chunk",
                                   spans.ns(r.admitted), spans.ns(now),
                                   r.qid)
            self._partial.append((r, [int(x) for x in done[r.streamed:n]],
                                  now))
            r.streamed = n

    def _retire_slot(self, slot: int, r: Request, now: float) -> None:
        """Finish ``slot``'s current occupant: hand it its tokens, free its
        pages. The caller decides what happens to the slot next (freed, or
        re-occupied by a staged request the segment pulled in)."""
        self._flush_stream(slot, r, now)
        r.tokens = np.asarray(
            self._gen.pop(slot)[: r.max_new_tokens], np.int32)
        r.latency = now - r.arrival
        self.stats["tokens_generated"] += len(r.tokens)
        self._slot_req[slot] = None
        if self._alloc is not None:
            # pages return to the pool the moment a sequence ends (the
            # prefix cache retains any it has indexed, rc permitting)
            self._alloc.release(slot)
            self._bt[slot, :] = self.n_pages
            self._bt_dev = None
        self._completed.append(r)

    def _try_spec_step(self) -> Optional[int]:
        """One speculative draft-then-verify round over the live slots;
        returns the window width (counted as ``spec_k + 1`` decode steps)
        on success, or ``None`` when the round must fall back to a normal
        fused segment.

        Protocol per slot at state ``(tok, pos)``: the draft proposes
        ``d_0..d_{k-1}`` greedily from ``tok``; the target teacher-forces
        ``[tok, d_0..d_{k-1}]`` at positions ``pos..pos+k`` in one
        dispatch and takes its own argmax ``preds`` at every window
        position. With ``m`` the first index where ``d_m != preds_m``
        (``m = k`` when all match), ``preds[0..m]`` are exactly the
        tokens plain greedy decode would emit — each one is the target's
        argmax given a prefix of already-accepted tokens — so the round
        emits ``m + 1`` genuine tokens and rewinds to state
        ``(preds[m], pos + m + 1)``. Stale KV past the rewind point is
        rewritten before any masked read can include it (the same
        invariant chunked admission relies on); recurrent families
        additionally re-seat the accepted prefix on a mismatch, because
        their O(1) state advanced through the rejected feeds.

        Gating is all-or-nothing — any live slot still feeding its
        seated prefix, within ``spec_k`` positions of its lifetime span
        (clamped window writes would corrupt valid KV), or whose pages
        cannot cover the window sends the whole round to the normal
        path. Requests therefore finish their last few tokens through
        ordinary segments.
        """
        k = self.spec_k
        plan: List[Tuple[int, Request, int]] = []
        for s, r in enumerate(self._slot_req):
            if r is None:
                continue
            pos = int(self._slot_pos[s])
            if pos + 1 < len(self._slot_prefix[s]):
                return None         # still feeding its seated prefix
            if pos + k > self._n_positions(r) - 1:
                return None         # window would write past its span
            plan.append((s, r, pos))
        if not plan:
            return None
        if self._alloc is not None:
            for s, _r, pos in plan:
                if not self._alloc.can_cover(s, pos + k + 1):
                    return None     # normal path handles pool pressure
            for s, _r, pos in plan:
                self._grow_slot(s, pos + k + 1)
        runner = self._spec
        # ---- draft propose (one dispatch) ----------------------------
        dr_tok = np.zeros((self.max_batch, 1), np.int32)
        dr_pos = np.zeros((self.max_batch,), np.int32)
        for s, r, pos in plan:
            credit = int(self._seat_credit[s])
            full = np.concatenate(
                [self._slot_prefix[s],
                 np.asarray(self._gen[s][credit:], np.int32)])
            assert len(full) == pos + 1, (s, len(full), pos)
            if not runner.in_sync(s, r, pos):
                runner.resync(s, full, pos)
            dr_tok[s, 0] = full[-1]
            dr_pos[s] = pos
        t_seg = time.perf_counter()
        drafts = runner.propose(jnp.asarray(dr_tok), jnp.asarray(dr_pos))
        # ---- target verify (one dispatch, no host sync in between) ---
        toks = jnp.concatenate([jnp.asarray(dr_tok), drafts], axis=1)
        verify = self._get_verify()
        if self._paged:
            if self._bt_dev is None:
                self._bt_dev = jnp.asarray(self._bt)
            self._cache, preds = verify(self.params, self._cache, toks,
                                        self._pos, self._bt_dev)
        else:
            self._cache, preds = verify(self.params, self._cache, toks,
                                        self._pos)
        drafts_np = np.asarray(drafts)
        preds_np = np.asarray(preds)            # the one blocking sync
        dt = time.perf_counter() - t_seg
        # ---- host accept loop ----------------------------------------
        now = time.perf_counter()
        new_tok = np.asarray(self._tok).copy()
        new_pos = np.asarray(self._pos).copy()
        new_rem = np.asarray(self._rem).copy()
        self.stats["decode_dispatches"] += 1
        self.stats["decode_steps"] += k + 1
        self.stats["spec_rounds"] += 1
        emitted = 0
        replay: List[int] = []
        seq_verify = self.model.decode_window is None
        for s, r, pos in plan:
            d = drafts_np[s]
            pr = preds_np[s]
            m = 0
            while m < k and d[m] == pr[m]:
                m += 1
            self.stats["spec_proposed"] += k
            self.stats["spec_accepted"] += m
            rem_s = int(new_rem[s])
            e = min(m + 1, rem_s)
            self._gen[s].extend(int(x) for x in pr[:e])
            emitted += e
            if e >= rem_s:
                new_rem[s] = 0
                runner.state[s] = None
                self._retire_slot(s, r, now)
                self._free.append(s)
                continue
            new_rem[s] = rem_s - e
            new_tok[s, 0] = int(pr[m])
            new_pos[s] = pos + m + 1
            self._slot_pos[s] = pos + m + 1
            # the draft's accepted rows hold exactly the accepted tokens
            # and its stale tail is rewritten before use: syncing is a
            # pure host-side marker update, no KV fix needed
            runner.state[s] = (r, pos + m + 1)
            if seq_verify and m < k:
                replay.append(s)
            self._flush_stream(s, r, now)
        self._tok = jnp.asarray(new_tok)
        self._pos = jnp.asarray(new_pos)
        self._rem = jnp.asarray(new_rem)
        # occupancy: the round did k+1 positions of target work per slot;
        # genuine progress is what survived verification — rejected
        # speculation shows up as bubble, exactly like idle slot-steps
        self.stats["busy_slot_steps"] += emitted
        self.stats["bubble_slot_steps"] += \
            (k + 1) * self.max_batch - emitted
        per = dt / max(1.0, emitted / len(plan))
        self._step_est = per if self._step_est == 0.0 \
            else 0.8 * self._step_est + 0.2 * per
        # recurrent-state rollback: sequential verify advanced the O(1)
        # states through the rejected feeds — re-seat the accepted prefix
        # through the teacher-forced replay path (bit-identical
        # continuation, same argument as preemption recovery)
        for s in replay:
            r = self._slot_req[s]
            credit = int(self._seat_credit[s])
            full = np.concatenate(
                [self._slot_prefix[s],
                 np.asarray(self._gen[s][credit:], np.int32)])
            self._seat_prefix(
                s, full, max(r.max_new_tokens - len(self._gen[s]), 1))
            self._seat_credit[s] = len(self._gen[s])
            self._slot_pos[s] = 0
            runner.state[s] = None
        self._register_prefix_pages()
        return k + 1

    def step(self) -> int:
        """One engine step: admit pending requests into free slots (staging
        the overflow into the device ring), run one fused decode segment,
        harvest finished slots — decoding the segment's completion log to
        split each slot's emission row between its successive occupants.
        Returns the number of decode steps executed (0 when idle).

        With ``speculate`` set, the step first attempts a speculative
        draft-then-verify round (``_try_spec_step``); segments remain the
        fallback for prefix feeding, end-of-request tails, and page
        pressure, so every request still completes."""
        with spans.span("engine.admit"):
            self._admit_pending()
        live = sum(r is not None for r in self._slot_req)
        if live == 0:
            return 0
        self.stats["peak_concurrency"] = max(
            self.stats["peak_concurrency"], live)
        if self._spec is not None:
            n = self._try_spec_step()
            if n is not None:
                return n
        if self._alloc is not None:
            # append pages ahead of the segment: each active slot's pos
            # advances by at most decode_block positions before the next
            # host boundary. Worst-case reservations pre-fund every cover;
            # optimistic admission can find the pool dry here, in which
            # case pressure relief un-stages queued work and then preempts
            # the slackest victim until the grow fits (it always does
            # eventually: a lone validated request fits the pool).
            for s, r in enumerate(self._slot_req):
                if r is None:
                    continue
                cover = min(int(self._slot_pos[s]) + self.decode_block,
                            self._n_positions(r))
                if not self._alloc.can_cover(s, cover):
                    self.stats["pressure_stalls"] += 1
                    while not self._alloc.can_cover(s, cover):
                        if not self._relieve_pressure(protect=s):
                            break
                self._grow_slot(s, cover)
        decode = self._get_decode()
        R = max(self.stage_slots, 1)
        if self._staged:
            ring_tok = np.zeros((R, self.max_len), np.int32)
            ring_plen = np.zeros((R,), np.int32)
            ring_new = np.zeros((R,), np.int32)
            ring_bt = KV.sentinel_block_table(
                R, self.pages_per_slot, self.n_pages) if self._paged \
                else None
            for j, (r, _ticket, bt_row) in enumerate(self._staged):
                ring_tok[j, :len(r.prompt)] = r.prompt
                ring_plen[j] = len(r.prompt)
                ring_new[j] = max(r.max_new_tokens, 1)
                if ring_bt is not None:
                    ring_bt[j] = bt_row
        else:
            # empty-ring steady state: reuse one device-resident zero
            # ring instead of re-uploading fresh host arrays per segment
            if self._ring0 is None:
                self._ring0 = (
                    jnp.zeros((R, self.max_len), jnp.int32),
                    jnp.zeros((R,), jnp.int32),
                    jnp.zeros((R,), jnp.int32),
                    jnp.asarray(KV.sentinel_block_table(
                        R, self.pages_per_slot, self.n_pages))
                    if self._paged else None)
            ring_tok, ring_plen, ring_new, ring_bt = self._ring0
        args = [self.params, self._cache, self._tok, self._pos, self._rem,
                self._plen, self._pbuf, ring_tok, ring_plen, ring_new,
                np.int32(len(self._staged))]
        if self._paged:
            # the block table rides to the device only when a host-side
            # write actually changed it (admission, growth, preemption,
            # COW); steady-state segments reuse the resident copy
            if self._bt_dev is None:
                self._bt_dev = jnp.asarray(self._bt)
            args += [self._bt_dev, ring_bt]
        t_seg = time.perf_counter()
        (self._cache, self._tok, self._pos, self._rem, self._plen,
         self._pbuf, out, comp_slot, comp_step, comp_adm, n_comp,
         busy_steps, n_steps) = decode(*args)
        self.stats["decode_dispatches"] += 1
        with spans.span("engine.sync"):
            out_np = np.asarray(out)                 # the one host sync
            comp_slot = np.asarray(comp_slot)
            comp_step = np.asarray(comp_step)
            comp_adm = np.asarray(comp_adm)
            n_comp = int(n_comp)
            n_steps = int(n_steps)
            busy_steps = int(busy_steps)
        if n_steps:
            # EWMA per-step wall time (all slots advance in lockstep):
            # feeds the slack policy's remaining-service estimate
            per = (time.perf_counter() - t_seg) / n_steps
            self._step_est = per if self._step_est == 0.0 \
                else 0.8 * self._step_est + 0.2 * per
        with spans.span("engine.harvest"):
            self._harvest(out_np, comp_slot, comp_step, comp_adm, n_comp,
                          n_steps, busy_steps)
        return n_steps

    def _harvest(self, out_np: np.ndarray, comp_slot: np.ndarray,
                 comp_step: np.ndarray, comp_adm: np.ndarray, n_comp: int,
                 n_steps: int, busy_steps: int) -> None:
        """Book a finished decode segment on the host: counters, then the
        completion log (retire, in-segment seats), emitted tokens, the
        streaming flush, and prefix-page registration."""
        self._slot_pos = np.asarray(self._pos).astype(np.int64)
        self.stats["decode_steps"] += n_steps
        self.stats["busy_slot_steps"] += busy_steps
        self.stats["bubble_slot_steps"] += \
            n_steps * self.max_batch - busy_steps
        now = time.perf_counter()
        # completion log, in segment order: each record closes the slot's
        # current occupant over out[slot, consumed:step+1]; an "admitted"
        # record then seats the next staged request (device admission is
        # FIFO over the ring, mirrored by popping self._staged in order)
        consumed = np.zeros((self.max_batch,), np.int64)
        for j in range(n_comp):
            s = int(comp_slot[j])
            t = int(comp_step[j])
            r = self._slot_req[s]
            row = out_np[s, consumed[s]:t + 1]
            self._gen[s].extend(int(x) for x in row[row >= 0])
            consumed[s] = t + 1
            self._retire_slot(s, r, now)
            if comp_adm[j]:
                nr, ticket, bt_row = self._staged.popleft()
                if self._alloc is not None:
                    self._alloc.rekey(ticket, s)
                    self._bt[s, :] = bt_row
                    self._bt_dev = None
                nr.admitted = now
                self._slot_req[s] = nr
                self._gen[s] = []
                self._slot_prefix[s] = np.asarray(nr.prompt, np.int32)
                self._seat_credit[s] = 0
                if self._prefix is not None:
                    # staged seats bypass the cache (pages can't attach
                    # mid-segment) but their prompt pages still register
                    self._seat_digests[s] = self._prefix.chain(nr.prompt)
                    self._reg_upto[s] = 0
                self.stats["admitted"] += 1
                self.stats["inseg_admissions"] += 1
            else:
                self._free.append(s)
        for s, r in enumerate(self._slot_req):
            if r is None:
                continue
            row = out_np[s, consumed[s]:]
            self._gen[s].extend(int(x) for x in row[row >= 0])
            self._flush_stream(s, r, now)
        # a prefilled request with max_new == 1 is complete at admission
        # (its only token came from prefill, rem == 0): it never passes
        # through the loop's refill logic, so sweep it here
        rem_np = np.asarray(self._rem)
        for s, r in enumerate(self._slot_req):
            if r is not None and rem_np[s] == 0:
                self._retire_slot(s, r, now)
                self._free.append(s)
        self._register_prefix_pages()

    def _register_prefix_pages(self) -> None:
        """Prefix registration: index every prompt page the last dispatch
        fully wrote (pos frontier crossed its end). Host bookkeeping only
        — the pool bytes were produced by that dispatch's device ops, so
        any later lookup's gather is ordered after them."""
        if self._prefix is None:
            return
        for s, r in enumerate(self._slot_req):
            if r is None or not self._seat_digests[s]:
                continue
            done = int(self._reg_upto[s])
            n_ready = min(int(self._slot_pos[s]) // self.page_size,
                          len(self._seat_digests[s]))
            if n_ready > done:
                self._prefix.register(
                    self._seat_digests[s][done:n_ready],
                    [int(p) for p in self._bt[s, done:n_ready]])
                self._reg_upto[s] = n_ready
        self.stats["evictions"] = self._alloc.evictions

    def drain_completions(self) -> List[Request]:
        """Return (and clear) the requests completed since the last drain."""
        out, self._completed = self._completed, []
        return out

    def drain_partial_outputs(self) -> List[Tuple[Request, List[int], float]]:
        """Return (and clear) ``(request, new_tokens, t_wall)`` chunks
        harvested since the last drain (``stream=True`` engines only).
        Chunks for one request appear in emission order, and across all
        drains their concatenation equals ``request.tokens`` exactly."""
        out, self._partial = self._partial, []
        return out

    @property
    def occupancy(self) -> Dict[str, float]:
        """Derived occupancy metrics over all fused segments so far:
        slot-busy fraction (active vs total slot-steps inside segments),
        in-segment admissions per segment, and the absolute bubble (idle
        slot-step) count. ``EngineExecutor`` snapshots deltas of these
        per run into its decision log."""
        busy = self.stats["busy_slot_steps"]
        bubble = self.stats["bubble_slot_steps"]
        segs = self.stats["decode_dispatches"]
        total = busy + bubble
        if self._alloc is not None:
            self.stats["evictions"] = self._alloc.evictions
        return {
            "slot_busy_frac": busy / total if total else 0.0,
            "admissions_per_segment":
                self.stats["inseg_admissions"] / segs if segs else 0.0,
            "bubble_slot_steps": float(bubble),
            "segments": float(segs),
            "prefix_hits": float(self.stats["prefix_hits"]),
            "prefix_pages_reused":
                float(self.stats["prefix_pages_reused"]),
            "cow_copies": float(self.stats["cow_copies"]),
            "evictions": float(self.stats["evictions"]),
            # speculative decoding: fraction of draft proposals the
            # target accepted (0 with speculation off / never attempted)
            "spec_rounds": float(self.stats["spec_rounds"]),
            "spec_accept_rate":
                self.stats["spec_accepted"] / self.stats["spec_proposed"]
                if self.stats["spec_proposed"] else 0.0,
        }

    def serve(self, reqs: Sequence[Request]) -> List[Request]:
        """Serve requests to completion: a thin closed loop over the
        open-loop core (submit all, step until done).

        Safe to interleave with open-loop use of the same engine: the loop
        stops once *these* requests are done, and completions of requests
        submitted by other callers stay queued for their
        ``drain_completions()``."""
        for r in reqs:
            self._validate(r)
        for r in reqs:
            self.submit(r)
        while self.busy and any(r.tokens is None for r in reqs):
            self.step()
        mine = {id(r) for r in reqs}
        self._completed = [r for r in self._completed
                           if id(r) not in mine]
        return list(reqs)

    # Legacy wave API (the JaxExecutor calibration path and older callers).
    def run_wave(self, reqs: Sequence[Request]) -> List[Request]:
        return self.serve(reqs)


# Explicit alias: the continuous engine is the default data plane.
ContinuousEngine = ServingEngine


class WaveEngine:
    """Seed-style run-to-completion wave engine (benchmark baseline).

    One prefill + per-token decode dispatches with a host sync every step;
    pads every wave to its longest prompt and decodes to the longest
    max_new; compiles per distinct (batch, prompt_len) shape. Kept verbatim
    (minus dead knobs) so ``benchmarks/fig_engine_throughput.py`` can
    measure the continuous engine against it.
    """

    def __init__(self, model: Model, params: Any, max_batch: int = 8,
                 pad_to: int = 32):
        self.model = model
        self.params = params
        self.max_batch = max_batch
        self.pad_to = pad_to
        self.stats: Dict[str, int] = {"prefill_traces": 0,
                                      "decode_traces": 0}

        def _prefill(p, b):
            self.stats["prefill_traces"] += 1
            return model.prefill(p, b)

        def _decode(p, c, t, pos):
            self.stats["decode_traces"] += 1
            return model.decode(p, c, t, pos)

        self._prefill = jax.jit(_prefill)
        self._decode = jax.jit(_decode)

    def _pad_cache(self, cache, batch: int, max_len: int):
        shapes = self.model.cache_shapes(batch, max_len, enc_len=self.pad_to)

        def pad(c, tgt):
            if c.shape == tgt.shape:
                return c.astype(tgt.dtype)
            pads = [(0, t - s) for s, t in zip(c.shape, tgt.shape)]
            return jnp.pad(c, pads).astype(tgt.dtype)
        return jax.tree.map(pad, cache, shapes)

    def run_wave(self, reqs: Sequence[Request]) -> List[Request]:
        """Serve one batch of requests to completion (greedy decoding)."""
        t0 = time.perf_counter()
        B = len(reqs)
        plen = max(len(r.prompt) for r in reqs)
        prompts = np.zeros((B, plen), np.int32)
        for i, r in enumerate(reqs):
            prompts[i, plen - len(r.prompt):] = r.prompt  # left-pad
        batch = {"tokens": jnp.asarray(prompts)}
        cfg = self.model.cfg
        if cfg.family == "audio":
            batch["frames"] = jnp.zeros((B, plen, cfg.d_model), cfg.dtype)
        if cfg.family == "vlm":
            batch["image_embeds"] = jnp.zeros(
                (B, cfg.n_image_tokens, cfg.d_model), cfg.dtype)
        logits, cache = self._prefill(self.params, batch)
        max_new = max(r.max_new_tokens for r in reqs)
        cache = self._pad_cache(cache, B, plen + max_new)
        out = np.zeros((B, max_new), np.int32)
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        for t in range(max_new):
            out[:, t] = np.asarray(tok[:, 0])
            logits, cache = self._decode(self.params, cache, tok, plen + t)
            tok = jnp.argmax(logits[:, -1], axis=-1).astype(
                jnp.int32)[:, None]
        jax.block_until_ready(logits)
        dt = time.perf_counter() - t0
        for i, r in enumerate(reqs):
            r.tokens = out[i, : r.max_new_tokens]
            r.latency = dt
        return list(reqs)

    def serve(self, reqs: Sequence[Request]) -> List[Request]:
        """Adaptive batching across waves of at most max_batch requests."""
        done: List[Request] = []
        pending = list(reqs)
        while pending:
            wave, pending = pending[: self.max_batch], \
                pending[self.max_batch:]
            done.extend(self.run_wave(wave))
        return done


class JaxExecutor:
    """Real executor for INFaaS workers: variant -> (engine, measured t(b)).

    Loads reduced-config models for the variants' architectures (host-sized)
    and measures actual wall-clock service times, which calibrate the
    simulator's profile-driven executor. ``execute`` warms the engine's
    compile caches for the request shape first, so measured service times
    are pure execution (the seed paid XLA compile time inside measurement).
    """

    def __init__(self, arch_cfgs: Dict[str, ArchConfig], seed: int = 0,
                 **engine_kwargs):
        self.engines: Dict[str, ServingEngine] = {}
        # keyed on (arch, batch, prompt_len): mixed-length calibration runs
        # are distinct measurements and must not overwrite each other
        self.measured: Dict[Tuple[str, int, int], float] = {}
        rng = jax.random.PRNGKey(seed)
        for name, cfg in arch_cfgs.items():
            model = build_model(cfg)
            params = model.init(rng)
            self.engines[name] = ServingEngine(model, params,
                                               **engine_kwargs)

    def execute(self, arch: str, batch: int, prompt_len: int = 8,
                max_new: int = 4) -> float:
        eng = self.engines[arch]
        eng.warmup(prompt_lens=[prompt_len])
        reqs = [Request(rid=i, prompt=np.arange(prompt_len) % 7,
                        max_new_tokens=max_new) for i in range(batch)]
        t0 = time.perf_counter()
        eng.serve(reqs)
        dt = time.perf_counter() - t0
        self.measured[(arch, batch, prompt_len)] = dt
        return dt
