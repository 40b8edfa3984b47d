"""Whole runs of the benchmark at a tiny size on the CPU: a sound run is
correct; the lower-precision control and every fault that a served cell
can have are not. The faults are planted underneath the timed path (the
engine's decode program), and the harness is driven as a run drives it,
past its look for a chip."""
import jax.numpy as jnp
import pytest

from chipbench_tiny import TINY_LIMIT, run_tiny

from repro.serving.engine import ServingEngine

CELL = "phi3-int8-chat"


@pytest.fixture(scope="module")
def controlled():
    """One run with the configuration's control (int4 weights in place of
    int8) put in the program's place after the window; the program's own
    reading of the same run is kept beside it."""
    keep = {}
    from chipbench_tiny import tiny
    cfg = tiny(CELL)[1]
    out = run_tiny(CELL, seed=2**40 + 3, control=cfg["control"], keep=keep)
    return out, keep


def test_sound_run_is_correct(controlled):
    out, keep = controlled
    assert out["program_correct"], out
    assert out["program_gap"] <= TINY_LIMIT
    assert out["attempted"] > 0 and out["failed"] == 0
    assert keep["gaps"]["tokens"] > 0
    assert list(out)[-1] == "checks"
    assert out["device"]["platform"] == "cpu"


def test_control_is_not_correct(controlled):
    """int4 weights put in the program's place come out not correct
    through the run's own checks, and read well above the sound run."""
    out, _keep = controlled
    assert out["correct"] is False
    gap = out["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"] == TINY_LIMIT
    assert gap["value"] > 3 * out["program_gap"]
    assert all(v["value"] <= v["limit"] for k, v in out["checks"].items()
               if k != "logit_gap")


def _wrap_decode(monkeypatch, alter):
    orig = ServingEngine._get_decode

    def patched(self):
        fn = orig(self)
        if getattr(fn, "_faulty", False):
            return fn

        def faulty(*args):
            return alter(args, fn(*args))
        faulty._faulty = True
        self._decode_fn = faulty
        return faulty
    monkeypatch.setattr(ServingEngine, "_get_decode", patched)


def test_token_altered_where_produced_is_caught(monkeypatch):
    """The first token each slot emits in every decode segment is shifted
    by one in the decode program's output (the answer, not what is fed
    back): about one served token in sixteen is wrong."""
    def alter(args, outs):
        out = outs[6]
        bad = jnp.where(out[:, 0] >= 0, (out[:, 0] + 1) % 256, out[:, 0])
        return outs[:6] + (out.at[:, 0].set(bad),) + outs[7:]
    _wrap_decode(monkeypatch, alter)
    out = run_tiny(CELL, seed=11)
    assert not out["correct"]
    assert out["checks"]["logit_gap"]["value"] > TINY_LIMIT


def test_step_returning_its_state_unchanged_is_caught(monkeypatch):
    """The decode segment hands back the KV cache it was given: no
    decoded token's keys and values are ever written."""
    def alter(args, outs):
        return (args[1],) + outs[1:]
    _wrap_decode(monkeypatch, alter)
    out = run_tiny(CELL, seed=12)
    assert not out["correct"]
    assert out["checks"]["logit_gap"]["value"] > TINY_LIMIT


def test_traced_run_reports_per_layer_metrics():
    out = run_tiny(CELL, seed=13, seconds=1.5, trace=True)
    assert out["correct"], out["checks"]
    assert {"mfu.chat", "slot_busy_frac.chat",
            "submit_ms_p90"} <= set(out["metrics"])
    # the CPU trace has no device plane: no roofline or idle share, and
    # never a 0 in their place
    assert "device_idle_share.chat" not in out["metrics"]
    assert "decode_segment_roofline.chat" not in out["metrics"]
    assert out["device"]["window_s"] > 0
