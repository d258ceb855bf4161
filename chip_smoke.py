#!/usr/bin/env python3
"""Chip smoke test: FLYCOO CPD-ALS on a TPU at the published size of vast.

Run from the root of a checkout, on a machine with a TPU:

    python3 chip_smoke.py              # one chip (the default)
    python3 chip_smoke.py --chips 4    # the sharded engine on a (4,) mesh

One chip drives the engine's main path through its public entry points:
``datasets.synthesize`` -> ``build_flycoo`` -> ``engine.init`` ->
``engine.all_modes`` with the ``pallas_fused`` compact kernels compiled
through Mosaic, then ``cp_als``. Tensor: Table 3's vast at its published
dims (5 modes, 26.0M generated nonzeros before dedupe), values and factors
made from ``--seed``. Checks:

  * every mode's MTTKRP agrees with the plain COO oracle ``mttkrp_ref``
    (computed on the chip over nonzero chunks) within KERNEL_TOL of each
    row's absolute-term sum ``sum |val * prod F|``;
  * the compiled all-modes program contains ``tpu_custom_call`` (the
    kernels ran on the chip, not interpreted or replaced by XLA);
  * ALS_SWEEPS CPD-ALS sweeps give finite fits that agree with the ``xla``
    backend's within FIT_TOL;
  * the resilience counters (degradations, recoveries, retries) read zero.

``--chips 4`` runs only the sharded path: one ``engine.dist`` rotation of
vast on a (4,) ``data`` mesh, checked per row against the oracle and the
one-chip engine on device 0 (both on the same sharded plans), and for
shard placement. Any failure exits non-zero and prints
no result line; a passing run ends with one JSON line naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(REPO, "src")

TENSOR = "vast"
RANK = 32
ALS_SWEEPS = 3
#: Per-row bound on |engine - oracle| over sum |val * prod F|. Reordered
#: f32 sums stay near 1e-7 of that sum; one bf16 MXU pass (the TPU default
#: precision) is off by ~2e-3 on rows with few nonzeros.
KERNEL_TOL = 1e-4
#: Bound on |fit(pallas_fused) - fit(xla)| per sweep: both run the same
#: f32 ALS algebra; only the MTTKRP summation order differs.
FIT_TOL = 1e-3
#: Nonzeros per step of the on-chip oracle (keeps its partials ~1 GiB).
REF_CHUNK = 1 << 20


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> None:
    print(f"FAIL: {msg}", flush=True)
    sys.exit(1)


def setup_jax(chips: int):
    """Import JAX, keep its compile cache at a fixed path (unless
    JAX_COMPILATION_CACHE_DIR names one) and insist on ``chips`` TPUs."""
    import jax

    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if cache is None:
        cache = os.path.join(REPO, ".jax_cache")
        os.makedirs(cache, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache)
    held = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    log(f"compile cache {cache}: {held} entries at start")
    try:
        devices = jax.devices()
    except RuntimeError as exc:
        fail(f"no TPU found: JAX could not initialize a backend ({exc})")
    if devices[0].platform != "tpu":
        fail(f"no TPU found: JAX sees {len(devices)} "
             f"{devices[0].platform} device(s); this smoke test runs only "
             "on a TPU and never falls back to the CPU or interpret mode")
    if len(devices) < chips:
        fail(f"--chips {chips} needs {chips} TPUs, JAX sees {len(devices)}")
    log(f"device: {devices[0].device_kind} x{len(devices)} "
        f"(jax {jax.__version__})")
    return jax, devices


def make_tensor(seed: int, build):
    from repro.core import datasets

    ts = datasets.spec(TENSOR, scale=1.0, max_nnz=None)
    t0 = time.perf_counter()
    indices, values = datasets.synthesize(ts, seed=seed)
    t_syn = time.perf_counter() - t0
    t0 = time.perf_counter()
    tensor = build(indices, values, ts.dims)
    t_plan = time.perf_counter() - t0
    log(f"tensor {TENSOR}: dims {ts.dims}, {ts.nnz} generated nonzeros, "
        f"nnz {tensor.nnz} after dedupe; host synthesis {t_syn:.1f} s, "
        f"FLYCOO plans {t_plan:.1f} s")
    log("blocks per mode: "
        + ", ".join(f"{p.nblocks} (kappa {p.kappa}, rows_pp {p.rows_pp})"
                    for p in tensor.plans))
    return tensor


def oracle(jax, tensor, factors):
    """Per-mode ``(ref, abs_sum)`` from ``mttkrp_ref`` on the chip: the
    plain COO oracle and the row sums of |val * prod F|, accumulated over
    REF_CHUNK-nonzero chunks so the partials fit in HBM."""
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from repro.core import mttkrp_ref

    nnz = tensor.nnz
    pad = -nnz % REF_CHUNK            # pad rows: index 0, value 0
    idx = jnp.asarray(np.pad(tensor.indices, ((0, pad), (0, 0))))
    val = jnp.asarray(np.pad(tensor.values, (0, pad)))

    @functools.partial(jax.jit, static_argnums=(3, 4))
    def chunked(idx, val, factors, mode, dim):
        absf = [jnp.abs(f) for f in factors]

        def body(c, acc):
            ix = lax.dynamic_slice_in_dim(idx, c * REF_CHUNK, REF_CHUNK)
            v = lax.dynamic_slice_in_dim(val, c * REF_CHUNK, REF_CHUNK)
            return (acc[0] + mttkrp_ref(ix, v, factors, mode, dim),
                    acc[1] + mttkrp_ref(ix, jnp.abs(v), absf, mode, dim))

        zero = jnp.zeros((dim, factors[0].shape[1]), jnp.float32)
        return lax.fori_loop(0, val.shape[0] // REF_CHUNK, body,
                             (zero, zero))

    return [tuple(np.asarray(a) for a in chunked(idx, val, tuple(factors),
                                                  d, tensor.dims[d]))
            for d in range(tensor.nmodes)]


def worst_row_ratio(out, ref, abs_sum) -> float:
    """max over rows/ranks of |out - ref| / sum|val * prod F| (a row with
    no nonzeros must match exactly)."""
    import numpy as np

    if out.shape != ref.shape:
        fail(f"output shape {out.shape} != oracle shape {ref.shape}")
    if not np.all(np.isfinite(out)):
        fail("non-finite MTTKRP output")
    tiny = np.finfo(np.float32).tiny
    return float(np.max(np.abs(out - ref) / np.maximum(abs_sum, tiny)))


def check_rows(label: str, outs, refs) -> None:
    import numpy as np

    for d, (out, (ref, abs_sum)) in enumerate(zip(outs, refs)):
        ratio = worst_row_ratio(np.asarray(out), ref, abs_sum)
        log(f"{label} mode {d}: worst |diff| / row abs-sum = {ratio:.3e} "
            f"(bound {KERNEL_TOL:g})")
        if not ratio <= KERNEL_TOL:
            fail(f"{label} mode {d} off the oracle: {ratio:.3e} > "
                 f"{KERNEL_TOL:g}")


def check_no_degradation() -> None:
    from repro.obs.metrics import REGISTRY

    for name in ("resilience_degradations", "resilience_recoveries",
                 "resilience_retries"):
        total = REGISTRY.counter(name).total()
        log(f"{name}: {total}")
        if total:
            fail(f"{name} = {total}: the run degraded")


def one_chip(jax, seed: int) -> None:
    import numpy as np

    from repro import engine
    from repro.core import build_flycoo, cp_als, init_factors
    from repro.engine import ExecutionConfig
    from repro.engine.stream import resident_bytes

    cfg = ExecutionConfig(backend="pallas_fused", interpret=False,
                          residency="full", schedule="compact", dedup=True,
                          fuse_remap=False)
    tensor = make_tensor(seed, functools.partial(
        build_flycoo, rows_pp=cfg.resolve_rows_pp(), block_p=cfg.block_p,
        schedule=cfg.schedule))
    log(f"resident_bytes {resident_bytes(tensor, cfg, RANK)} "
        f"(rank {RANK})")

    t0 = time.perf_counter()
    state = jax.block_until_ready(engine.init(tensor, cfg))
    log(f"engine.init {time.perf_counter() - t0:.1f} s (host clock: "
        "dedup tables + upload)")

    factors = tuple(init_factors(jax.random.PRNGKey(seed), tensor.dims,
                                 RANK))
    t0 = time.perf_counter()
    outs, state = engine.all_modes(state, factors)
    outs = [np.asarray(o) for o in outs]
    log(f"engine.all_modes, first call {time.perf_counter() - t0:.1f} s "
        "(host clock, compile included)")
    hlo = engine.scan_hlo(state, factors)
    kernels = hlo.count('custom_call_target="tpu_custom_call"')
    log(f"compiled all_modes program: {kernels} tpu_custom_call op(s)")
    if kernels < tensor.nmodes:
        fail(f"expected a Mosaic kernel per mode in the compiled program, "
             f"found {kernels}")
    del state, hlo

    t0 = time.perf_counter()
    refs = oracle(jax, tensor, factors)
    log(f"oracle {time.perf_counter() - t0:.1f} s (host clock)")
    check_rows("pallas_fused", outs, refs)
    del outs, refs

    key = jax.random.PRNGKey(seed)
    fits = {}
    for backend in ("pallas_fused", "xla"):
        t0 = time.perf_counter()
        res = cp_als(tensor, RANK, iters=ALS_SWEEPS, key=key,
                     config=dataclasses.replace(cfg, backend=backend),
                     ladder=False)
        fits[backend] = res.fits
        log(f"cp_als {backend}: fits {res.fits} "
            f"({time.perf_counter() - t0:.1f} s host clock, compile "
            "included)")
        del res
    fp, fx = np.asarray(fits["pallas_fused"]), np.asarray(fits["xla"])
    if fp.shape != (ALS_SWEEPS,) or not np.all(np.isfinite(fp)) \
            or not np.all(np.isfinite(fx)):
        fail(f"ALS fits missing or not finite: {fits}")
    gap = float(np.max(np.abs(fp - fx)))
    log(f"ALS fit gap pallas_fused vs xla: {gap:.3e} (bound {FIT_TOL:g})")
    if not gap <= FIT_TOL:
        fail(f"ALS fits disagree: {gap:.3e} > {FIT_TOL:g}")
    check_no_degradation()


def four_chips(jax, devices, seed: int) -> None:
    import numpy as np

    from repro import engine
    from repro.core import init_factors
    from repro.core.distributed import build_sharded_flycoo
    from repro.engine import ExecutionConfig
    from repro.launch.mesh import make_mesh

    cfg = ExecutionConfig(backend="pallas_fused", interpret=False,
                          residency="full", schedule="compact", dedup=True,
                          fuse_remap=False)
    tensor = make_tensor(seed, functools.partial(
        build_sharded_flycoo, n_dev=4, block_p=cfg.block_p))
    factors = tuple(init_factors(jax.random.PRNGKey(seed), tensor.dims,
                                 RANK))

    t0 = time.perf_counter()
    outs1, _ = engine.all_modes(engine.init(tensor, cfg), factors)
    outs1 = [np.asarray(o) for o in outs1]
    log(f"one-chip engine on {devices[0]}: {time.perf_counter() - t0:.1f} s "
        "(host clock, compile included)")

    mesh = make_mesh((4,), ("data",))
    t0 = time.perf_counter()
    dstate = jax.block_until_ready(
        engine.dist.shard_state(engine.init(tensor, cfg, _rotating=True),
                                mesh))
    log(f"engine.init + shard_state {time.perf_counter() - t0:.1f} s")
    mesh_devices = set(mesh.devices.flat)
    for leaf in jax.tree.leaves((dstate.val, dstate.idx, dstate.alpha,
                                 dstate.sched)):
        on = {s.device for s in leaf.addressable_shards}
        if leaf.sharding.device_set != mesh_devices or on != mesh_devices:
            fail(f"a sharded {leaf.shape} array sits on {sorted(map(str, on))}"
                 ", not on every mesh device")
    log(f"sharded layout: every array spread over {len(mesh_devices)} "
        "devices")

    t0 = time.perf_counter()
    outs4, dstate = engine.dist.dist_all_modes(dstate, factors)
    outs4 = [np.asarray(o) for o in outs4]
    log(f"engine.dist.dist_all_modes, first call "
        f"{time.perf_counter() - t0:.1f} s (host clock, compile included)")
    del dstate

    refs = oracle(jax, tensor, factors)
    check_rows("one chip, sharded plans", outs1, refs)
    check_rows("dist (4 chips)", outs4, refs)
    check_rows("dist (4 chips) vs one chip",
               outs4, [(o, a) for o, (_, a) in zip(outs1, refs)])
    check_no_degradation()


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip path (default); 4: only the "
                    "sharded engine on a (4,) mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(SRC, "repro")):
        fail(f"the repro package is not at {SRC}: run chip_smoke.py from "
             "a checkout of the repository")
    sys.path.insert(0, SRC)
    jax, devices = setup_jax(args.chips)
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(jax, devices, args.seed)
    else:
        one_chip(jax, args.seed)
    log(f"total {time.perf_counter() - t0:.1f} s")
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
