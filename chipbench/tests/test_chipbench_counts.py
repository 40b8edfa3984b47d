"""Operations and bytes the roofline shares divide by, against values
worked by hand from published shapes: the benchmark's configuration, and
a grouped-attention bfloat16 shape (Yi-9B's widths, arXiv:2403.04652,
24 layers) for the counts' other paths."""
import chipbench_tiny
from harness import counts, spec
from harness import weights as W

GQA_BF16 = W.Dims(layers=24, d=4096, heads=32, kv_heads=4, head_dim=128,
                  ff=11008, vocab=64000, rope_theta=5e6, eps=1e-6,
                  projections="bfloat16")


def _dims(name):
    return W.dims(spec.config(spec.load_benchmark(), name))


def test_phi3_mini_int8_by_hand():
    dm = _dims("phi3-mini-int8")
    # per layer: q, k, v, o 3072x3072 each (32 MHA heads of 96) and
    # gate, up, down 3072x8192: 4*9,437,184 + 3*25,165,824 = 113,246,208
    assert counts.proj_params(dm) == 32 * 113_246_208 == 3_623_878_656
    # int8 projections + a float32 scale per output channel
    # (3072*4 + 8192*2 + 3072 = 31,744 a layer), bf16 head 32064x3072,
    # bf16 norms (2 a layer + final) of 3072
    assert counts.weight_bytes(dm) == (3_623_878_656 + 4 * 32 * 31_744
                                       + 2 * 32_064 * 3072
                                       + 2 * 3072 * 65)
    # K and V of one position: 32 layers x 2 x 32 heads x 96 x 2 bytes
    assert counts.kv_bytes_per_token(dm) == 393_216
    # a 100-token prompt: 2*P*100 projections, 100*101/2 attention pairs
    # at 4*H*D per pair per layer, the head once
    assert counts.prefill_flops(dm, 100) == (
        2 * 3_623_878_656 * 100 + 32 * 4 * 32 * 96 * 5050
        + 2 * 32_064 * 3072)
    # the decode step of token 3 of a 100-token prompt reads 103 positions
    f, kv = counts.request_decode(dm, 100, [0, 3])
    assert f == 2 * 3_623_878_656 + 32 * 4 * 3072 * 103 + 2 * 32_064 * 3072
    assert kv == 103 * 393_216 + 2 * 3072


def test_gqa_bf16_by_hand():
    dm = GQA_BF16
    # per layer: q and o 4096x4096, k and v 4096x512 (4 KV heads of 128),
    # gate, up, down 4096x11008:
    # 2*16,777,216 + 2*2,097,152 + 3*45,088,768 = 173,015,040
    assert counts.proj_params(dm) == 24 * 173_015_040 == 4_152_360_960
    assert counts.weight_bytes(dm) == (2 * 4_152_360_960
                                       + 2 * 64_000 * 4096
                                       + 2 * 4096 * 49)
    # 4.15 B projections + 0.26 B head in bf16: 8.83 GB every decode step
    assert 8.8e9 < counts.weight_bytes(dm) < 8.9e9
    # GQA: K and V of 4 heads of 128 in 24 layers, bf16
    assert counts.kv_bytes_per_token(dm) == 24 * 2 * 4 * 128 * 2 == 49_152
    # attention FLOPs count query heads (32), not KV heads
    assert counts.attn_flops(dm, 10) == 24 * 4 * 32 * 128 * 10


def test_no_work_for_the_prefill_token():
    dm = GQA_BF16
    assert counts.request_decode(dm, 300, [0]) == (0, 0)
