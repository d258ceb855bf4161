"""fig11: weak-scaling multi-device sweep (engine.dist) + out-of-core
streaming oversubscription points (engine.stream).

For 1/2/4/8 fake CPU devices, grow the tensor with the device count
(fixed nnz and mode-0 rows per device) and measure one distributed
all-modes rotation plus the per-mode remap-exchange wire traffic of the
two strategies: the precomputed collective_permute schedule vs the
all_gather-the-element-list baseline. Traffic comes from the static
:class:`~repro.engine.dist.ExchangeSchedule` (host-side truth — identical
on real hardware); wall-clock runs in a subprocess so each point gets its
own ``--xla_force_host_platform_device_count``.

The streaming section (:func:`run_stream`, env knob
``STREAM_BUDGET_BYTES``) runs the same tensor resident and streamed under
budgets that oversubscribe it, verifying bitwise equality and recording
the transfer-bytes / overlap-efficiency / peak-ring curves the CI
``stream-smoke`` job gates.

Rows: ``fig11/weak_scale_dev{n},us_per_call,permute_KB=..;all_gather_KB=..``
and ``fig11/stream_oversub_b{i},us_per_call,budget_KB=..;...`` with the
full byte splits recorded in ``benchmarks/out/results.json``.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from .common import emit, memory_probe, time_fn

DEVICES = (1, 2, 4, 8)
NNZ_PER_DEV = 3000
DIM0_PER_DEV = 96
SRC = os.path.join(os.path.dirname(__file__), "..", "src")

# Device budget for the streaming points; the default (and a 4x tighter
# second point) oversubscribes the synthetic tensor below, so the curve
# always exercises real chunking. CI sets it artificially tiny.
STREAM_BUDGET_BYTES = int(os.environ.get("STREAM_BUDGET_BYTES",
                                         256 * 1024))
STREAM_NNZ = 12_000
STREAM_DIMS = (384, 128, 96)
STREAM_RANK = 16

_CHILD = """
import os
n_dev = int(os.environ["FIG11_NDEV"])
os.environ["XLA_FLAGS"] = \\
    f"--xla_force_host_platform_device_count={n_dev}"
import json, time
import jax
import numpy as np
from repro import engine
from repro.core import init_factors
from repro.core.distributed import build_sharded_flycoo
from repro.engine.dist import exchange_bytes
from repro.launch.mesh import make_mesh

nnz = int(os.environ["FIG11_NNZ"])
dims = (int(os.environ["FIG11_DIM0"]), 64, 48)
rng = np.random.default_rng(0)
idx = np.unique(np.stack([rng.integers(0, d, nnz) for d in dims], 1)
                .astype(np.int32), axis=0)
val = rng.standard_normal(idx.shape[0]).astype(np.float32)
t = build_sharded_flycoo(idx, val, dims, n_dev=n_dev, rows_pp=8, block_p=8)
factors = tuple(init_factors(jax.random.PRNGKey(0), dims, 16))
state = engine.init(t)
if n_dev == 1:
    st, run = state, lambda s: engine.all_modes(s, factors)
    per_mode = [dict(mode=d, permute_bytes=0, all_gather_bytes=0)
                for d in range(len(dims))]
else:
    mesh = make_mesh((n_dev,), ("data",))
    st = engine.dist.shard_state(state, mesh)
    per_mode = exchange_bytes(st.schedule, len(dims), st.slocs)
    run = lambda s: engine.dist.dist_all_modes(s, factors)
outs, st = run(st)  # compile + warm
jax.block_until_ready(outs)
ts = []
for _ in range(3):
    t0 = time.perf_counter()
    outs, st = run(st)
    jax.block_until_ready(outs)
    ts.append(time.perf_counter() - t0)
print(json.dumps({"us": float(np.median(ts)) * 1e6,
                  "nnz": int(val.shape[0]), "per_mode": per_mode}))
"""


def _point(n_dev: int) -> dict:
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               PYTHONPATH=SRC + os.pathsep + os.environ.get("PYTHONPATH", ""),
               FIG11_NDEV=str(n_dev),
               FIG11_NNZ=str(NNZ_PER_DEV * n_dev),
               FIG11_DIM0=str(DIM0_PER_DEV * n_dev))
    out = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                         capture_output=True, text=True, timeout=900)
    if out.returncode != 0:
        raise RuntimeError(f"fig11 child (n_dev={n_dev}) failed:\n"
                           f"{out.stderr}")
    return json.loads(out.stdout.splitlines()[-1])


def _stream_row(i: int, budget: int, tensor, factors, outs_res) -> tuple:
    """One oversubscription point: stream the tensor under ``budget``,
    check bitwise parity against the resident outputs, time a warm
    rotation, and record the transfer/residency stats."""
    import numpy as np

    from repro.engine.config import ExecutionConfig
    from repro.engine.stream import (resident_bytes, stream_all_modes,
                                     stream_init, stream_transfer_model)

    config = ExecutionConfig(backend="xla", rows_pp=8,
                             device_budget_bytes=budget,
                             rank_hint=STREAM_RANK)
    state = stream_init(tensor, config)
    outs, state = stream_all_modes(state, factors)
    bitwise = all(np.array_equal(np.asarray(a), np.asarray(b))
                  for a, b in zip(outs_res, outs))
    if not bitwise:
        raise RuntimeError(
            f"streamed rotation diverged from resident engine "
            f"(budget={budget})")
    stats = state.stats.as_row()          # first-rotation snapshot
    resident = resident_bytes(tensor, config)

    holder = {"state": state}

    def rotation():
        outs, holder["state"] = stream_all_modes(holder["state"], factors)
        return outs

    us = time_fn(rotation, warmup=1) * 1e6
    name = f"fig11/stream_oversub_b{i}"
    derived = (f"budget_KB={budget / 1024:.0f}"
               f";oversub_x={resident / budget:.2f}"
               f";peak_ring_KB={stats['peak_ring_bytes'] / 1024:.1f}"
               f";transfer_KB={stats['transfer_bytes'] / 1024:.1f}"
               f";overlap={stats['overlap_efficiency']:.2f}")
    return (name, us, derived, {
        "budget_bytes": budget,
        "resident_bytes": resident,
        "oversubscription_x": resident / budget,
        "bitwise_equal": bitwise,
        "chunks_per_rotation": stats["chunks_streamed"],
        "modeled_transfer": stream_transfer_model(tensor, config),
        **stats,
        **memory_probe(),
    })


def run_stream() -> None:
    """The streaming oversubscription points alone (the CI ``stream-smoke``
    entry — no fake multi-device subprocesses needed)."""
    import jax
    import numpy as np

    from repro import engine
    from repro.core import init_factors
    from repro.core.flycoo import build_flycoo
    from repro.engine.config import ExecutionConfig

    rng = np.random.default_rng(0)
    idx = np.unique(
        np.stack([rng.integers(0, d, STREAM_NNZ) for d in STREAM_DIMS], 1)
        .astype(np.int32), axis=0)
    val = rng.standard_normal(idx.shape[0]).astype(np.float32)
    tensor = build_flycoo(idx, val, STREAM_DIMS, rows_pp=8)
    factors = tuple(init_factors(jax.random.PRNGKey(0), STREAM_DIMS,
                                 STREAM_RANK))
    outs_res, _ = engine.all_modes(
        engine.init(tensor, ExecutionConfig(backend="xla", rows_pp=8)),
        factors)
    rows = [_stream_row(i, budget, tensor, factors, outs_res)
            for i, budget in enumerate(
                (STREAM_BUDGET_BYTES, STREAM_BUDGET_BYTES // 4))]
    emit(rows)


def run() -> None:
    import jax

    # The weak-scale points run on fake CPU devices in child processes and
    # the stream points run here; on an accelerator this process would
    # hold the device while its children start, so refuse up front.
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            "fig11 runs fake-CPU-device children next to this process; "
            f"the {jax.default_backend()} backend serves one process at a "
            "time. Rerun with JAX_PLATFORMS=cpu.")
    rows = []
    for n_dev in DEVICES:
        rec = _point(n_dev)
        pk = sum(m["permute_bytes"] for m in rec["per_mode"]) / 1024
        ak = sum(m["all_gather_bytes"] for m in rec["per_mode"]) / 1024
        rows.append((
            f"fig11/weak_scale_dev{n_dev}",
            rec["us"],
            f"permute_KB_per_dev={pk:.1f};all_gather_KB_per_dev={ak:.1f}",
            {"n_dev": n_dev, "nnz": rec["nnz"],
             "per_mode_exchange": rec["per_mode"]},
        ))
    emit(rows)
    run_stream()
