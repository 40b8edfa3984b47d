"""The entry point without a chip, and imports that stay off the TPU."""
import os
import shutil
import subprocess
import sys

import chipbench_tiny

CHECKOUT = os.path.dirname(chipbench_tiny.BENCH)
RUN = ["chipbench/run.py", "--workload", "phi3-int8-chat", "--seed",
       "4294967311", "--seconds", "1", "--trace", "0"]


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def test_no_tpu_exits_nonzero_and_prints_no_result():
    p = subprocess.run([sys.executable] + RUN, cwd=CHECKOUT, env=_env(),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    """A directory with only BENCHMARK.json and chipbench/ (no program)."""
    shutil.copy(os.path.join(CHECKOUT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(chipbench_tiny.BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable] + RUN, cwd=tmp_path, env=_env(),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_imports_load_no_tpu_library():
    """Importing the harness, every metric reader and the references
    starts no JAX backend and maps no libtpu into the process."""
    code = (
        "import sys; sys.path[:0] = ['src', 'chipbench']\n"
        "from harness import spec, cell, check, serve, tracing, roofline\n"
        "b = spec.load_benchmark()\n"
        "for m in b['end_to_end'] + b['per_layer']: spec.reader(m['name'])\n"
        "for c in b['configs']: spec.reference(spec.config(b, c['name']))\n"
        "import jax._src.xla_bridge as xb\n"
        "assert not xb._backends, xb._backends\n"
        "maps = open('/proc/self/maps').read()\n"
        "assert 'libtpu' not in maps\n"
        "print('ok')\n")
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    p = subprocess.run([sys.executable, "-c", code], cwd=CHECKOUT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip() == "ok"
