"""Shares of the chip's peak, from needed work (``harness.counts``) and
device time (``harness.tracing``). Each returns None when there is
nothing to read, never 0."""
from __future__ import annotations

from typing import Optional, Sequence

from harness import counts, tracing

# XLA module names of the engine's jitted programs. The contiguous-KV
# decode segment is jitted through a lambda, so its module is named for it.
PREFILL = ("prefill_admit",)
DECODE = ("decode_segment", "_lambda")


def _device_time(ctx, patterns: Sequence[str]) -> float:
    if ctx.trace is None or not ctx.trace.devices:
        return 0.0
    t, _ = tracing.module_time(ctx.trace.devices[0], patterns)
    return t


def decode_share(ctx) -> Optional[float]:
    """Needed decode bytes (weights as stored once per step, K and V of
    live positions) at HBM bandwidth over the decode program's device
    time, in %."""
    dev_t = _device_time(ctx, DECODE)
    steps = ctx.delta("decode_steps", traced=True)
    if dev_t <= 0 or steps <= 0:
        return None
    w = ctx.work(ctx.in_trace)
    need = steps * counts.weight_bytes(ctx.dm) + w["decode_kv_bytes"]
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / dev_t


def prefill_share(ctx) -> Optional[float]:
    """Needed prefill time (the larger of the real prompt tokens' FLOPs at
    the bf16 peak and the bytes every dispatch must read at HBM
    bandwidth) over the prefill program's device time, in %."""
    dev_t = _device_time(ctx, PREFILL)
    disp = ctx.delta("prefill_dispatches", traced=True)
    w = ctx.work(ctx.in_trace)
    if dev_t <= 0 or disp <= 0 or w["prefills"] == 0:
        return None
    t_flops = w["prefill_flops"] / ctx.peaks["bf16_flops"]
    t_bytes = (disp * counts.weight_bytes(ctx.dm) + w["prefill_kv_bytes"]) \
        / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * max(t_flops, t_bytes) / dev_t


def step_mfu(ctx) -> Optional[float]:
    """Model FLOPs delivered in the window (prefill plus decode, needed
    work only) over the window times the bf16 peak, in %."""
    w = ctx.work(ctx.in_window)
    flops = w["prefill_flops"] + w["decode_flops"]
    if flops <= 0:
        return None
    return 100.0 * flops / (ctx.seconds * ctx.peaks["bf16_flops"])
