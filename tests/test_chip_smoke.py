"""``chip_smoke.py`` rehearsed on the CPU at the reduced config.

The script's ``main`` refuses anything but a TPU; its phases take the
config, so they run here on ``llama3.2-1b``'s smoke config: the same
control-plane, reference and streaming checks, and the teardown between
phases that the full-width run relies on to free the first cluster's
weights before the second builds its own.
"""
import gc
import importlib.util
import pathlib
import subprocess
import sys

import pytest

from repro.configs.registry import ARCHS

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_phases_pass_on_reduced_config(smoke):
    import dataclasses
    cfg = ARCHS["llama3.2-1b"].reduced()
    ecfg = dataclasses.replace(smoke.engine_config(), max_len=256)
    gc.collect()
    before = smoke.live_device_bytes()
    # the analytic profiles of a 64-wide model put it on the host CPU
    assert smoke.serve_virtual(cfg, ecfg, hardware="cpu-host") == \
        smoke.N_VIRTUAL
    gc.collect()
    # the first cluster's params, caches and engines are all released
    assert smoke.live_device_bytes() <= before + 2**20
    smoke.serve_wall(cfg, ecfg, hardware="cpu-host")


def test_refuses_cpu_without_a_result():
    p = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"},
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"ok": true' not in p.stdout
    assert "needs a TPU" in p.stderr
