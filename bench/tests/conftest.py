"""Fixtures of the benchmark's tests: a throwaway checkout that holds the
benchmark, the program and one more cell, ``tiny.als_r32``, small enough
for the CPU (Pallas kernels in interpret mode)."""
import json
import os
import shutil

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY_CONFIG = {
    "name": "tiny", "dims": [40, 30, 8, 6], "nnz": 3000, "zipf_a": 1.2,
    "sweeps_per_start": 2,
    "engine": {"backend": "pallas_fused", "interpret": None,
               "residency": "full", "fuse_remap": False},
}


@pytest.fixture
def tiny_root(tmp_path):
    """A checkout with ``tiny.als_r32`` added by files and entries only:
    its configuration, a traffic file of its own and the uber cell's
    limits."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(REPO, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", "__pycache__",
                                                  "tests"))
    os.symlink(os.path.join(REPO, "src"), root / "src")
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "bench/configs/tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny.als_r8", "config": "tiny",
                               "traffic": "als_r8", "chips": 1,
                               "why": "test"})
    for m in bench["per_layer"]:
        m["workloads"].append("tiny.als_r8")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "bench" / "configs" / "tiny.json").write_text(
        json.dumps(TINY_CONFIG))
    (root / "bench" / "traffic" / "als_r8.json").write_text(
        json.dumps({"rank": 8}))
    shutil.copy(root / "bench" / "limits" / "uber.als_r32.json",
                root / "bench" / "limits" / "tiny.als_r8.json")
    return str(root)
