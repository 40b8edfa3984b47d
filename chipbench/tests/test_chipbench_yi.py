"""The Yi-9B pipeline-stage cell (``yi-l24-batch16``) at a tiny size on the
CPU: its reference against the program's bfloat16 grouped-attention model,
a whole closed-loop run that comes out correct while its control does
not, the per-layer metrics of a traced run, and its configuration's
counts at published widths."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_tiny import CPU_PEAKS, TINY_LIMIT, tiny
from harness import cell, counts, spec
from harness import weights as W
from harness.serve import arch_config, to_program_params

from repro.models.model import build_model

CELL = "yi-l24-batch16"
# as in test_chipbench_reference: both sides in float32, so only the order
# of summation differs
ATOL = 2e-4


@pytest.mark.parametrize("kv_heads", [1, 2])
def test_prefill_then_decode_matches_yi_reference(kv_heads):
    """bfloat16 projections, query heads grouped over fewer KV heads:
    prefill, then greedy decode through the program's cache, gives the
    logits ``yi_ref`` gives for the whole sequence at once."""
    _bench, cfg, _mix, _c = tiny(CELL, kv_heads)
    assert cfg["precision"]["projections"] == "bfloat16"
    cfg["precision"]["activations"] = "float32"
    dm = W.dims(cfg)
    model = build_model(arch_config(cfg))
    seed = 2**37 + 5
    params = to_program_params(jax.tree.map(
        lambda a: a.astype(jnp.float32), W.make(dm, seed)))
    ref = spec.reference(cfg)
    prompt = np.random.default_rng(1).integers(0, dm.vocab, 13)
    prompt = prompt.astype(np.int32)
    logits, cache = model.prefill(params, {"tokens": jnp.asarray(prompt)[None]})
    cache = jax.tree.map(
        lambda c: jnp.pad(c, [(0, 0)] * 2 + [(0, 32 - c.shape[2])]
                          + [(0, 0)] * (c.ndim - 3)), cache)
    toks, got = [], [np.asarray(logits[0, -1])]
    tok = int(jnp.argmax(logits[0, -1]))
    for i in range(5):
        toks.append(tok)
        lg, cache = model.decode(params, cache, jnp.array([[tok]], jnp.int32),
                                 jnp.int32(len(prompt) + i))
        got.append(np.asarray(lg[0, -1]))
        tok = int(jnp.argmax(lg[0, -1]))
    seq = np.concatenate([prompt, np.array(toks, np.int32)])

    outer = W.outer(dm, seed)
    x = ref.embed(jnp.asarray(seq), outer["embed"])
    for i in range(dm.layers):
        lw = W.layer(dm, seed, i)
        w = {n: ref.dequant(lw[n], W.CONTRACT[n]) for n in W.CONTRACT}
        w.update(ln1=lw["ln1"], ln2=lw["ln2"])
        x = ref.layer(x, w, dm.rope_theta, dm.eps)
    want = np.asarray(ref.logits(x[len(prompt) - 1:], outer["ln_f"],
                                 outer["head"], dm.eps))
    np.testing.assert_allclose(np.stack(got), want, atol=ATOL, rtol=0)


@pytest.fixture(scope="module")
def controlled():
    """One traced closed-loop run of the tiny cell (grouped heads) with the
    configuration's control put in the program's place after the window."""
    bench, cfg, mix, c = tiny(CELL, kv_heads=1)
    out = cell.run(bench, CELL, 2**40 + 77, 2.0, True, time.perf_counter(),
                   require_chip=False, cfg=cfg, mix=mix, cell=c,
                   peaks=CPU_PEAKS, cache=False, control=cfg["control"])
    return out


def test_sound_run_is_correct(controlled):
    out = controlled
    assert out["program_correct"], out
    assert out["program_gap"] <= TINY_LIMIT
    assert out["attempted"] > 0 and out["failed"] == 0


def test_control_is_not_correct(controlled):
    out = controlled
    assert out["correct"] is False
    gap = out["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"] == TINY_LIMIT
    assert all(v["value"] <= v["limit"] for k, v in out["checks"].items()
               if k != "logit_gap")


def test_traced_run_reports_the_cells_metrics(controlled):
    got = set(controlled["metrics"])
    assert {"mfu.batch", "slot_busy_frac.batch", "prefill_pad_share.batch",
            "submit_ms_p90", "ttft_p50_s.batch", "ttft_p90_s.batch",
            "tpot_p90_ms.batch"} <= got
    # a closed loop of 16 over 8 slots admits groups at padded buckets
    assert 0.0 < controlled["metrics"]["prefill_pad_share.batch"]["value"] \
        < 1.0
    # the CPU trace has no device plane: no roofline or idle share
    assert not {"device_idle_share.batch", "decode_segment_roofline.batch",
                "prefill_admit_roofline"} & got
    assert not any(k.endswith(".chat") for k in got)


class _Ctx:
    def __init__(self, stats):
        self.stats = stats

    def delta(self, key, traced=False):
        return self.stats.get(key, 0)


@pytest.mark.parametrize("stats,want", [
    ({"prefill_prompt_tokens": 700, "prefill_computed_tokens": 2048}, 0.658),
    ({"prefill_prompt_tokens": 512, "prefill_computed_tokens": 512}, 0.0),
    ({}, None),          # a program without the counters
])
def test_prefill_pad_share_reader(stats, want):
    v = spec.reader("prefill_pad_share.batch")(_Ctx(stats))
    assert v == want if want is None else abs(v - want) < 1e-3


def test_yi_stage_counts_at_published_widths():
    """24 layers of Yi-9B's widths with the head and the embedding: the
    weights, the KV of a position and the cache of the deployment."""
    cfg = spec.config(spec.load_benchmark(), "yi-9b-l24-bf16")
    dm = W.dims(cfg)
    assert (dm.d, dm.heads, dm.kv_heads, dm.head_dim, dm.ff, dm.vocab) == \
        (4096, 32, 4, 128, 11008, 64000)
    assert counts.proj_params(dm) == 24 * 173_015_040
    # projections, head and embedding in bf16, plus 49 norms of 4096
    params = counts.proj_params(dm) + 2 * 64_000 * 4096 + 49 * 4096
    assert params == 4_676_849_664
    assert 2 * params == 9_353_699_328
    assert counts.kv_bytes_per_token(dm) == 49_152
    dep = cfg["deployment"]
    assert dep["slots"] * dep["max_len"] * 49_152 == 805_306_368
    assert dep["variant"] == f"{dep['arch']}/tpu-v5e-1/bf16-b{dep['slots']}"
