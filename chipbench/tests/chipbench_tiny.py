"""A cell of BENCHMARK.json cut to a size the CPU runs in seconds, for the
tests that drive a whole run without a chip. Widths, depth and vocabulary
shrink; the structure of the configuration, its precision, its traffic
mix's shape and the harness path stay."""
import copy
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
sys.path.insert(0, BENCH)

from harness import cell, spec  # noqa: E402

CPU_PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}
# widest served-token gap a sound tiny run may show. Readings at this
# size (CPU): sound runs 0.000-0.012, the int4 control 0.26-0.29, a
# planted fault about 1 (a wrong token against logits whose spread is
# about 1). The limit sits between them.
TINY_LIMIT = 0.04


def tiny(name: str, kv_heads: int = 4):
    """The cell cut to a tiny size; ``kv_heads`` below 4 makes its
    attention grouped (GQA)."""
    bench = spec.load_benchmark()
    wl = spec.workload(bench, name)
    cfg = copy.deepcopy(spec.config(bench, wl["config"]))
    cfg.update(hidden_size=64, intermediate_size=128,
               num_attention_heads=4, num_key_value_heads=kv_heads,
               head_dim=16, num_hidden_layers=2, vocab_size=256)
    dep = cfg["deployment"]
    dep["arch"] = "tiny-" + dep["arch"]
    dep["variant"] = dep["arch"] + "/tpu-v5e-1/" + \
        dep["variant"].split("/")[-1]
    dep.update(max_len=128, min_bucket=16)
    mix = copy.deepcopy(spec.traffic(wl["traffic"]))
    for k, med, lo, hi in (("prompt_len", 24, 8, 64),
                           ("output_len", 16, 8, 48)):
        mix[k].pop("mean", None)
        mix[k].update(median=med, min=lo, max=hi)
    mix.update(ramp_s=0.5, drain_cap_s=30)
    mix["arrivals"]["rate_per_s"] = 4.0
    c = {"sample": 3, "logit_gap_limit": TINY_LIMIT, "trace_s": 1.0}
    return bench, cfg, mix, c


def run_tiny(name: str, seed: int, seconds: float = 2.0, trace=False,
             **kw):
    bench, cfg, mix, c = tiny(name)
    return cell.run(bench, name, seed, seconds, trace, time.perf_counter(),
                    require_chip=False, cfg=cfg, mix=mix, cell=c,
                    peaks=CPU_PEAKS, cache=False, **kw)
