import os
import shutil
import subprocess
import sys

import pytest

from bench import run
from bench.tests.conftest import REPO


def _run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "uber.als_r32",
         "--seed", "0", "--seconds", "10", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_a_cpu_is_refused():
    import jax

    with pytest.raises(run.NoChip, match="no TPU found"):
        run.tpu_devices(jax, 1)


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run(REPO)
    assert p.returncode != 0
    assert "no TPU found" in p.stderr
    assert '"correct"' not in p.stdout


def test_a_checkout_without_the_program_exits_nonzero(tmp_path):
    shutil.copytree(os.path.join(REPO, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    p = _run(tmp_path)
    assert p.returncode != 0 and '"correct"' not in p.stdout


def test_seed_out_of_range_is_refused():
    with pytest.raises(SystemExit):
        run.parse_args(["--workload", "x", "--seed", "-1", "--seconds",
                        "1"])
