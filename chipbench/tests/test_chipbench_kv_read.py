"""``decode_kv_read_share``: the share of the KV positions decode attention
could read that it did read, from the engine's counters. The reader on
hand-made counters, and a tiny traced run on the CPU, where decode takes
XLA attention and so reads every position."""
import pytest

from chipbench_tiny import run_tiny
from harness import spec


class _Ctx:
    def __init__(self, stats):
        self.stats = stats

    def delta(self, key, traced=False):
        return self.stats.get(key, 0)


@pytest.mark.parametrize("stats,want", [
    ({"decode_kv_positions_read": 1024,
      "decode_kv_positions_full": 16 * 8 * 512}, 0.015625),
    ({"decode_kv_positions_read": 4096, "decode_kv_positions_full": 4096},
     1.0),
    ({}, None),          # a program without the counters
])
def test_decode_kv_read_share_reader(stats, want):
    v = spec.reader("decode_kv_read_share")(_Ctx(stats))
    assert v == want


@pytest.mark.parametrize("name", ["phi3-int8-chat", "yi-l24-batch16"])
def test_traced_tiny_run_reports_decode_kv_read_share(name):
    out = run_tiny(name, seed=2**40 + 161, seconds=1.5, trace=True)
    assert out["metrics"]["decode_kv_read_share"]["value"] == 1.0
