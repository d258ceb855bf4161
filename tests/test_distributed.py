"""Multi-device tests (subprocess with 8 fake CPU devices).

The test process keeps 1 device (conftest); anything needing a mesh runs in
a fresh interpreter with XLA_FLAGS set before jax import.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(body: str, devices: int = 8, timeout: int = 900) -> str:
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count={devices}"
        import jax
        import jax.numpy as jnp
        import numpy as np
    """) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert out.returncode == 0, f"stdout:{out.stdout}\nstderr:{out.stderr}"
    return out.stdout


def test_distributed_mttkrp_matches_oracle():
    """Deprecated shim: oracle parity on a (data=4, model=2) mesh, plus the
    regressions of the shim rework — ``all_modes`` from a mid-rotation
    mode (the old class hard-asserted ``current_mode == 0``) and
    ``reset()`` for parity with the ``MTTKRPExecutor`` shim."""
    out = run_sub("""
        import warnings
        from repro.core.distributed import (DistributedMTTKRP,
                                            build_sharded_flycoo)
        from repro.core import init_factors, mttkrp_ref
        from repro.launch.mesh import make_mesh

        rng = np.random.default_rng(0)
        dims = (40, 30, 20)
        idx = np.unique(np.stack(
            [rng.integers(0, d, 1500) for d in dims], 1).astype(np.int32),
            axis=0)
        val = rng.standard_normal(idx.shape[0]).astype(np.float32)
        mesh = make_mesh((4, 2), ("data", "model"))
        t = build_sharded_flycoo(idx, val, dims, n_dev=4, rows_pp=8,
                                 block_p=8)
        factors = init_factors(jax.random.PRNGKey(1), dims, 8)
        with warnings.catch_warnings():
            warnings.simplefilter("error", DeprecationWarning)
            try:
                DistributedMTTKRP(t, mesh)
            except DeprecationWarning:
                pass
            else:
                raise AssertionError("shim must warn DeprecationWarning")
        exe = DistributedMTTKRP(t, mesh, model_axis="model")
        refs = [mttkrp_ref(jnp.asarray(idx), jnp.asarray(val), factors, d,
                           dims[d]) for d in range(3)]
        for sweep in range(2):
            outs = exe.all_modes(factors)
            for d in range(3):
                np.testing.assert_allclose(np.asarray(outs[d]), refs[d],
                                           rtol=2e-4, atol=2e-4)
        # step to mode 1, run all_modes mid-rotation (was an assert), reset
        np.testing.assert_allclose(np.asarray(exe.step(factors)), refs[0],
                                   rtol=2e-4, atol=2e-4)
        assert exe.current_mode == 1
        outs = exe.all_modes(factors)
        assert exe.current_mode == 1
        for d in range(3):
            np.testing.assert_allclose(np.asarray(outs[d]), refs[d],
                                       rtol=2e-4, atol=2e-4)
        exe.reset()
        assert exe.current_mode == 0
        np.testing.assert_allclose(np.asarray(exe.step(factors)), refs[0],
                                   rtol=2e-4, atol=2e-4)
        print("DIST_MTTKRP_OK")
    """)
    assert "DIST_MTTKRP_OK" in out


def test_engine_dist_matches_single_device():
    """engine.dist parity: nmodes 3-5 on 2 and 4 fake devices, against both
    the single-device engine and the COO oracle, across two sweeps."""
    out = run_sub("""
        from repro import engine
        from repro.core import init_factors, mttkrp_ref
        from repro.core.distributed import build_sharded_flycoo
        from repro.launch.mesh import make_mesh

        rng = np.random.default_rng(0)
        for nmodes, dims in ((3, (24, 18, 12)), (4, (12, 10, 8, 6)),
                             (5, (9, 8, 7, 6, 5))):
            idx = np.unique(np.stack(
                [rng.integers(0, d, 700) for d in dims], 1).astype(np.int32),
                axis=0)
            val = rng.standard_normal(idx.shape[0]).astype(np.float32)
            factors = tuple(init_factors(jax.random.PRNGKey(1), dims, 8))
            t = build_sharded_flycoo(idx, val, dims, n_dev=4, rows_pp=4,
                                     block_p=8)
            state = engine.init(t)
            outs_1d, _ = engine.all_modes(state, factors)
            refs = [mttkrp_ref(jnp.asarray(idx), jnp.asarray(val), factors,
                               d, dims[d]) for d in range(nmodes)]
            for n_dev in (2, 4):
                mesh = make_mesh((n_dev,), ("data",))
                ds = engine.dist.shard_state(state, mesh)
                for sweep in range(2):
                    outs, ds = engine.dist.dist_all_modes(ds, factors)
                    for d in range(nmodes):
                        np.testing.assert_allclose(
                            np.asarray(outs[d]), np.asarray(outs_1d[d]),
                            rtol=1e-5, atol=1e-5)
                        np.testing.assert_allclose(
                            np.asarray(outs[d]), refs[d], rtol=2e-4,
                            atol=2e-4)
                # single-mode stepping matches too
                out, ds = engine.dist.dist_mttkrp(ds, factors)
                np.testing.assert_allclose(np.asarray(out), refs[0],
                                           rtol=2e-4, atol=2e-4)
                assert ds.mode == 1
        print("ENGINE_DIST_OK")
    """)
    assert "ENGINE_DIST_OK" in out


def test_engine_dist_pallas_fused_backend_parity():
    """The sharded path drives fusing backends through the SAME plain-EC
    contract as every other backend (the remap stays the cross-device
    exchange): pallas_fused under dist_all_modes matches the oracle."""
    out = run_sub("""
        from repro import engine
        from repro.core import init_factors, mttkrp_ref
        from repro.core.distributed import build_sharded_flycoo
        from repro.launch.mesh import make_mesh

        rng = np.random.default_rng(2)
        dims = (24, 18, 12)
        idx = np.unique(np.stack(
            [rng.integers(0, d, 700) for d in dims], 1).astype(np.int32),
            axis=0)
        val = rng.standard_normal(idx.shape[0]).astype(np.float32)
        factors = tuple(init_factors(jax.random.PRNGKey(1), dims, 8))
        t = build_sharded_flycoo(idx, val, dims, n_dev=4, rows_pp=4,
                                 block_p=8)
        refs = [mttkrp_ref(jnp.asarray(idx), jnp.asarray(val), factors, d,
                           dims[d]) for d in range(3)]
        cfg = engine.ExecutionConfig(backend="pallas_fused", interpret=True)
        state = engine.init(t, cfg, _rotating=True)
        mesh = make_mesh((4,), ("data",))
        ds = engine.dist.shard_state(state, mesh)
        for sweep in range(2):
            outs, ds = engine.dist.dist_all_modes(ds, factors)
            for d in range(3):
                np.testing.assert_allclose(np.asarray(outs[d]), refs[d],
                                           rtol=2e-4, atol=2e-4)
        print("DIST_FUSED_OK")
    """, devices=4)
    assert "DIST_FUSED_OK" in out


def test_permute_schedule_matches_all_gather_baseline():
    """The collective_permute schedule and the all_gather baseline must
    produce bitwise-identical next layouts and outputs, the scanned
    program must compile ONCE per config, and the lowered permute program
    must contain collective_permute with no element-list all_gather."""
    out = run_sub("""
        from repro import engine
        from repro.core import init_factors
        from repro.core.distributed import build_sharded_flycoo
        from repro.engine.dist import DistConfig, lowered_text
        from repro.launch.mesh import make_mesh

        rng = np.random.default_rng(3)
        dims = (24, 18, 12)
        idx = np.unique(np.stack(
            [rng.integers(0, d, 900) for d in dims], 1).astype(np.int32),
            axis=0)
        val = rng.standard_normal(idx.shape[0]).astype(np.float32)
        factors = tuple(init_factors(jax.random.PRNGKey(1), dims, 8))
        t = build_sharded_flycoo(idx, val, dims, n_dev=4, rows_pp=4,
                                 block_p=8)
        state = engine.init(t)
        mesh = make_mesh((4,), ("data",))

        # ---- bitwise: permute vs all_gather layouts + outputs ----
        ds_p = engine.dist.shard_state(state, mesh,
                                       DistConfig(exchange="permute"))
        ds_a = engine.dist.shard_state(state, mesh,
                                       DistConfig(exchange="all_gather"))
        np.testing.assert_array_equal(np.asarray(ds_p.alpha),
                                      np.asarray(ds_a.alpha))
        for sweep in range(2):
            outs_p, ds_p = engine.dist.dist_all_modes(ds_p, factors)
            outs_a, ds_a = engine.dist.dist_all_modes(ds_a, factors)
            for a, b in zip(outs_p, outs_a):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            np.testing.assert_array_equal(np.asarray(ds_p.val),
                                          np.asarray(ds_a.val))
            np.testing.assert_array_equal(np.asarray(ds_p.idx),
                                          np.asarray(ds_a.idx))
            np.testing.assert_array_equal(np.asarray(ds_p.alpha),
                                          np.asarray(ds_a.alpha))

        # ---- one compile per distributed sweep config ----
        engine.reset_counters()
        # distinct pad_hop -> distinct jit cache entry: counts start fresh
        ds = engine.dist.shard_state(state, mesh, DistConfig(pad_hop=16))
        for _ in range(3):
            outs, ds = engine.dist.dist_all_modes(ds, factors)
        assert engine.TRACE_COUNTS["dist_all_modes"] == 1, \
            dict(engine.TRACE_COUNTS)
        assert engine.DISPATCH_COUNTS["dist_all_modes"] == 3, \
            dict(engine.DISPATCH_COUNTS)

        # ---- lowering: collective_permute, no element-list all_gather ----
        ds = engine.dist.shard_state(state, mesh)
        txt = lowered_text(ds, factors)
        assert "collective_permute" in txt
        sloc = ds.smax_loc
        for line in txt.splitlines():
            if "all_gather" in line:   # only the rows-x-R output gather
                assert f"tensor<{sloc}x" not in line, line
        txt_a = lowered_text(engine.dist.shard_state(
            state, mesh, DistConfig(exchange="all_gather")), factors)
        assert "collective_permute" not in txt_a
        assert any(f"tensor<{sloc}x" in line
                   for line in txt_a.splitlines() if "all_gather" in line)
        print("EXCHANGE_OK")
    """)
    assert "EXCHANGE_OK" in out


def test_dist_cp_als_single_traced_sweeps():
    """cp_als(mesh=...) runs distributed ALS sweeps through the dist fold
    hook and matches the single-device result; the whole run compiles the
    distributed sweep exactly once."""
    out = run_sub("""
        from repro import engine
        from repro.core import cp_als
        from repro.core.distributed import build_sharded_flycoo
        from repro.launch.mesh import make_mesh

        rng = np.random.default_rng(7)
        dims = (24, 18, 12)
        idx = np.unique(np.stack(
            [rng.integers(0, d, 900) for d in dims], 1).astype(np.int32),
            axis=0)
        val = rng.standard_normal(idx.shape[0]).astype(np.float32)
        t = build_sharded_flycoo(idx, val, dims, n_dev=4, rows_pp=4,
                                 block_p=8)
        mesh = make_mesh((4,), ("data",))
        engine.reset_counters()
        res_d = cp_als(t, rank=6, iters=4, mesh=mesh)
        assert engine.TRACE_COUNTS["dist_all_modes"] == 1
        assert engine.DISPATCH_COUNTS["dist_all_modes"] == 4
        res_s = cp_als(t, rank=6, iters=4)
        np.testing.assert_allclose(res_d.fits, res_s.fits, atol=2e-3)
        for a, b in zip(res_d.factors, res_s.factors):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-3)
        print("DIST_CPD_OK")
    """)
    assert "DIST_CPD_OK" in out


def test_dist_mode_shorter_than_mesh():
    """A mode with fewer rows than devices (vast's 2-row mode on 4 chips)
    shards with one partition per device, the surplus ones empty; the
    distributed rotation still matches the oracle on every mode."""
    out = run_sub("""
        from repro import engine
        from repro.core import init_factors, mttkrp_ref
        from repro.core.distributed import build_sharded_flycoo
        from repro.engine import ExecutionConfig
        from repro.launch.mesh import make_mesh

        rng = np.random.default_rng(3)
        dims = (40, 2, 24, 3)
        idx = np.unique(np.stack(
            [rng.integers(0, d, 1200) for d in dims], 1).astype(np.int32),
            axis=0)
        val = rng.standard_normal(idx.shape[0]).astype(np.float32)
        t = build_sharded_flycoo(idx, val, dims, n_dev=4, rows_pp=8,
                                 block_p=8)
        assert [p.kappa for p in t.plans][1::2] == [4, 4]
        factors = tuple(init_factors(jax.random.PRNGKey(0), dims, 6))
        mesh = make_mesh((4,), ("data",))
        for backend in ("xla", "pallas_fused"):
            st = engine.dist.shard_state(engine.init(
                t, ExecutionConfig(backend=backend, interpret=True),
                _rotating=True), mesh)
            outs, st = engine.dist.dist_all_modes(st, factors)
            for d in range(len(dims)):
                ref = mttkrp_ref(jnp.asarray(idx), jnp.asarray(val),
                                 factors, d, dims[d])
                np.testing.assert_allclose(outs[d], ref, rtol=1e-4,
                                           atol=1e-4)
        print("SHORT_MODE_OK")
    """, devices=4)
    assert "SHORT_MODE_OK" in out


def test_exchange_schedule_is_static_upper_bound():
    """Host-only (no mesh): the precomputed schedule's per-hop capacities
    bound the true cross-device move counts from the FLYCOO plans, are
    padded to the requested multiple, and feed the traffic model."""
    import numpy as np

    from repro.core.distributed import build_sharded_flycoo
    from repro.engine.dist import (element_devices, exchange_bytes,
                                   row_bytes, schedule_for_plans)

    rng = np.random.default_rng(2)
    dims = (40, 30, 20)
    idx = np.unique(np.stack(
        [rng.integers(0, d, 1200) for d in dims], 1).astype(np.int32),
        axis=0)
    val = rng.standard_normal(idx.shape[0]).astype(np.float32)
    n = len(dims)
    for schedule in ("compact", "rect"):
        t = build_sharded_flycoo(idx, val, dims, n_dev=4, rows_pp=8,
                                 block_p=8, schedule=schedule)
        for p in t.plans:
            assert p.kappa % 4 == 0
        for n_dev, pad in ((2, 8), (4, 4)):
            sched = schedule_for_plans(t.plans, n_dev, pad_hop=pad)
            assert sched.n_dev == n_dev
            assert len(sched.hops) == n
            for d in range(n):
                src = element_devices(t.plans[d], n_dev)
                dst = element_devices(t.plans[(d + 1) % n], n_dev)
                if schedule == "rect":
                    # rect: device ownership degenerates to the slot stride
                    np.testing.assert_array_equal(
                        src, t.plans[d].slot_of_elem
                        // (t.plans[d].padded_nnz // n_dev))
                assert len(sched.hops[d]) == n_dev - 1
                for h in range(1, n_dev):
                    cap = sched.hops[d][h - 1]
                    assert cap % pad == 0 or cap == 0
                    for k in range(n_dev):
                        moved = int(np.sum((src == k)
                                           & (dst == (k + h) % n_dev)))
                        assert moved <= cap, (d, h, k, moved, cap)
            slocs = [p.padded_nnz // n_dev for p in t.plans]
            rows = exchange_bytes(sched, n, slocs)
            for d, r in enumerate(rows):
                assert r["permute_bytes"] == \
                    sched.permute_slots(d) * row_bytes(n)
                # the baseline gathers each remote device's mode-d list
                assert r["all_gather_bytes"] == \
                    (n_dev - 1) * slocs[d] * row_bytes(n)
                # the whole point: the schedule ships (far) fewer bytes
                assert r["permute_bytes"] <= r["all_gather_bytes"]
        with pytest.raises(ValueError, match="not divisible"):
            schedule_for_plans(t.plans, 3)


def test_dist_compact_matches_rect_bitwise():
    """Device-major numbering over the compact layout: the distributed
    rotation on a skewed tensor is bitwise-identical to the rect-schedule
    baseline (and to the single-device compact engine), while using fewer
    local slots per device."""
    out = run_sub("""
        from repro import engine
        from repro.core import datasets, init_factors
        from repro.core.distributed import build_sharded_flycoo
        from repro.launch.mesh import make_mesh

        dims = (48, 36, 24)
        ts = datasets.TensorSpec(name="zipf", dims=dims, nnz=2500,
                                 zipf_a=1.5)
        idx, val = datasets.synthesize(ts, seed=3)
        factors = tuple(init_factors(jax.random.PRNGKey(1), dims, 8))
        mesh = make_mesh((4,), ("data",))
        states, douts, slocs = {}, {}, {}
        for schedule in ("compact", "rect"):
            t = build_sharded_flycoo(idx, val, dims, n_dev=4, rows_pp=4,
                                     block_p=8, schedule=schedule)
            state = engine.init(t)
            outs_1d, _ = engine.all_modes(state, factors)
            ds = engine.dist.shard_state(state, mesh)
            slocs[schedule] = ds.smax_loc
            acc = []
            for sweep in range(2):
                outs, ds = engine.dist.dist_all_modes(ds, factors)
                acc += [np.asarray(o) for o in outs]
            douts[schedule] = acc
            for d in range(3):  # dist == single-device, bitwise
                np.testing.assert_array_equal(acc[d],
                                              np.asarray(outs_1d[d]))
        for a, b in zip(douts["compact"], douts["rect"]):
            np.testing.assert_array_equal(a, b)
        assert slocs["compact"] < slocs["rect"], slocs
        print("DIST_COMPACT_OK", slocs)
    """, devices=4)
    assert "DIST_COMPACT_OK" in out


def test_sharded_train_step_matches_single_device():
    out = run_sub("""
        import dataclasses
        from repro import configs, sharding as shlib
        from repro.launch.mesh import make_mesh
        from repro.training import (OptimizerConfig, SyntheticLM,
                                    init_state, make_train_step)

        cfg = configs.smoke("tinyllama-1.1b")
        ocfg = OptimizerConfig(lr=1e-3, warmup_steps=1, total_steps=10)
        data = SyntheticLM(cfg, batch=4, seq=32, seed=0)
        batch = data.next()
        state = init_state(cfg, ocfg, jax.random.PRNGKey(0))
        # single device reference
        _, m_ref = jax.jit(make_train_step(cfg, ocfg))(
            jax.tree.map(jnp.copy, state), batch)
        # 2x4 mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        ctx = shlib.make_ctx(mesh)
        with shlib.use(ctx):
            _, m_sh = jax.jit(make_train_step(cfg, ocfg))(state, batch)
        a, b = float(m_ref["loss"]), float(m_sh["loss"])
        assert abs(a - b) < 3e-2, (a, b)
        print("SHARDED_TRAIN_OK", a, b)
    """)
    assert "SHARDED_TRAIN_OK" in out


def test_moe_expert_parallel_matches_local():
    out = run_sub("""
        from repro import configs, sharding as shlib
        from repro.launch.mesh import make_mesh
        from repro.models.moe import apply_moe, init_moe, _apply_local
        import dataclasses

        cfg = dataclasses.replace(configs.smoke("olmoe-1b-7b"),
                                  capacity_factor=8.0)
        params = init_moe(cfg, jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model),
                              jnp.bfloat16)
        ref = _apply_local(params, x, cfg)
        mesh = make_mesh((2, 4), ("data", "model"))
        ctx = shlib.make_ctx(mesh)
        with shlib.use(ctx):
            out = jax.jit(lambda p, t: apply_moe(p, t, cfg))(params, x)
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                    - ref.astype(jnp.float32))))
        assert err < 2e-2, err
        print("MOE_EP_OK", err)
    """)
    assert "MOE_EP_OK" in out


def test_gradient_compression_error_feedback():
    out = run_sub("""
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.training.compression import compressed_grad_sync

        mesh = make_mesh((4,), ("pod",))
        g_global = jax.random.normal(jax.random.PRNGKey(0), (4, 64, 128))

        sm = partial(jax.shard_map, mesh=mesh, in_specs=(P("pod"), P()),
                     out_specs=(P("pod"), P("pod")), check_vma=False)

        def body(g_shard, key):
            g = {"w": g_shard[0]}
            synced, err = compressed_grad_sync(g, key, rank=16, axis_name="pod")
            return synced["w"][None], err["w"][None]

        synced, err = jax.jit(sm(body))(g_global, jax.random.PRNGKey(1))
        true_mean = jnp.mean(g_global, axis=0)
        # every pod agrees on the synced value
        assert float(jnp.max(jnp.abs(synced - synced[0][None]))) < 1e-5
        # rank-16 approx of a rank-128 mean won't be exact; error feedback
        # must store the residual g + e - approx
        resid = g_global[0] - synced[0]
        np.testing.assert_allclose(np.asarray(err[0]), np.asarray(resid),
                                   rtol=1e-4, atol=1e-4)
        print("COMPRESS_OK")
    """)
    assert "COMPRESS_OK" in out


def test_dryrun_entry_small_mesh():
    """dryrun lower path works end to end on a small mesh in-process."""
    out = run_sub("""
        import dataclasses
        from repro.launch.dryrun import lower_cell
        from repro.launch.mesh import make_mesh
        from repro.configs import smoke

        cfg = smoke("tinyllama-1.1b")
        mesh = make_mesh((2, 4), ("data", "model"))
        rec = lower_cell("tinyllama-1.1b", "train_4k", cfg=dataclasses.replace(
            cfg, remat="full"), mesh=mesh)
        assert rec["cost"]["flops_per_device"] > 0
        assert rec["collectives_per_device"]["total"] > 0
        print("DRYRUN_SMALL_OK")
    """)
    assert "DRYRUN_SMALL_OK" in out


def test_pipeline_parallel_matches_sequential():
    out = run_sub("""
        from repro.launch.mesh import make_mesh
        from repro.training.pipeline import pipeline_apply

        n_stages, d = 4, 16
        mesh = make_mesh((4,), ("pp",))
        key = jax.random.PRNGKey(0)
        ws = jax.random.normal(key, (n_stages, d, d)) * 0.3

        def stage_fn(w, h):
            return jnp.tanh(h @ w)

        x = jax.random.normal(jax.random.PRNGKey(1), (8, d))
        # sequential reference
        ref = x
        for s in range(n_stages):
            ref = stage_fn(ws[s], ref)
        y = jax.jit(lambda w, t: pipeline_apply(
            stage_fn, w, t, mesh=mesh, n_micro=4))(ws, x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        print("PIPELINE_OK")
    """)
    assert "PIPELINE_OK" in out


def test_dist_exchange_rung_bitwise():
    """Injected collective_permute failure steps the exchange rung
    ``permute -> all_gather`` mid-run; final factors stay bitwise-equal
    to an undisturbed permute run (the PR-3 exchange parity, now a
    resilience guarantee)."""
    out = run_sub("""
        from repro import engine, obs
        from repro.core import cp_als
        from repro.core.distributed import build_sharded_flycoo
        from repro.launch.mesh import make_mesh
        from repro.resilience import ChaosSpec, LadderPolicy, install

        rng = np.random.default_rng(0)
        dims = (24, 18, 12)
        idx = np.unique(np.stack(
            [rng.integers(0, d, 600) for d in dims], 1).astype(np.int32),
            axis=0)
        val = rng.standard_normal(idx.shape[0]).astype(np.float32)
        t = build_sharded_flycoo(idx, val, dims, n_dev=4, rows_pp=4,
                                 block_p=8)
        mesh = make_mesh((4,), ("data",))
        clean = cp_als(t, rank=4, iters=4, mesh=mesh)

        install(ChaosSpec(exchange_fail=1))   # 2nd permute dispatch dies
        pol = LadderPolicy(backoff_base_s=1e-4, backoff_cap_s=1e-3)
        res = cp_als(t, rank=4, iters=4, mesh=mesh, ladder=pol)
        for a, b in zip(clean.factors, res.factors):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert clean.fits == res.fits
        degr = obs.REGISTRY.metrics()[
            "resilience_degradations"].as_dict()
        assert degr.get("exchange:permute->all_gather", 0) == 1, degr
        rep = obs.resilience_report()
        assert "exchange_fail" in rep["answered"]
        assert rep["unanswered"] == []
        print("EXCHANGE_RUNG_OK")
    """, devices=4)
    assert "EXCHANGE_RUNG_OK" in out


def test_dist_device_loss_shrinks_mesh_bitwise():
    """Losing 2 of 4 devices mid-run rebuilds the engine on the surviving
    2-device mesh from the latest snapshot and finishes bitwise-equal to
    an undisturbed 4-device run."""
    out = run_sub("""
        import tempfile
        from repro import engine, obs
        from repro.core import cp_als
        from repro.core.distributed import build_sharded_flycoo
        from repro.launch.mesh import make_mesh
        from repro.resilience import ChaosSpec, LadderPolicy, install

        rng = np.random.default_rng(0)
        dims = (24, 18, 12)
        idx = np.unique(np.stack(
            [rng.integers(0, d, 600) for d in dims], 1).astype(np.int32),
            axis=0)
        val = rng.standard_normal(idx.shape[0]).astype(np.float32)
        t = build_sharded_flycoo(idx, val, dims, n_dev=4, rows_pp=4,
                                 block_p=8)
        mesh = make_mesh((4,), ("data",))
        clean = cp_als(t, rank=4, iters=5, mesh=mesh)

        install(ChaosSpec(device_lost=2, device_lost_n=2))
        pol = LadderPolicy(backoff_base_s=1e-4, backoff_cap_s=1e-3)
        res = cp_als(t, rank=4, iters=5, mesh=mesh, ladder=pol,
                     checkpoint=tempfile.mkdtemp())
        for a, b in zip(clean.factors, res.factors):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert clean.fits == res.fits
        degr = obs.REGISTRY.metrics()[
            "resilience_degradations"].as_dict()
        assert degr.get("device_lost:4->2", 0) == 1, degr
        rep = obs.resilience_report()
        assert "device_lost" in rep["answered"]
        assert rep["unanswered"] == []
        # without a ladder the loss is fatal, never silent
        install(ChaosSpec(device_lost=0))
        try:
            cp_als(t, rank=4, iters=2, mesh=mesh)
        except Exception as exc:
            assert "injected loss" in str(exc)
        else:
            raise AssertionError("device loss must raise without ladder")
        print("DEVICE_LOSS_OK")
    """, devices=4)
    assert "DEVICE_LOSS_OK" in out


def test_dist_transient_dispatch_retries_bitwise():
    """A transiently failing dist dispatch retries with seeded backoff
    (the stream-upload path, at the dist hook site) and converges to the
    clean run bitwise."""
    out = run_sub("""
        from repro import engine, obs
        from repro.core import cp_als
        from repro.core.distributed import build_sharded_flycoo
        from repro.launch.mesh import make_mesh
        from repro.resilience import ChaosSpec, LadderPolicy, install

        rng = np.random.default_rng(0)
        dims = (24, 18, 12)
        idx = np.unique(np.stack(
            [rng.integers(0, d, 600) for d in dims], 1).astype(np.int32),
            axis=0)
        val = rng.standard_normal(idx.shape[0]).astype(np.float32)
        t = build_sharded_flycoo(idx, val, dims, n_dev=4, rows_pp=4,
                                 block_p=8)
        mesh = make_mesh((2,), ("data",))
        clean = cp_als(t, rank=4, iters=3, mesh=mesh)

        install(ChaosSpec(dist_transient=1, dist_transient_times=2))
        pol = LadderPolicy(backoff_base_s=1e-4, backoff_cap_s=1e-3)
        res = cp_als(t, rank=4, iters=3, mesh=mesh, ladder=pol)
        for a, b in zip(clean.factors, res.factors):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        retries = obs.REGISTRY.metrics()["resilience_retries"].as_dict()
        assert retries.get("dist.dispatch", 0) == 2, retries
        rep = obs.resilience_report()
        assert "dist_transient" in rep["answered"]
        assert rep["unanswered"] == []
        print("DIST_TRANSIENT_OK")
    """, devices=4)
    assert "DIST_TRANSIENT_OK" in out


# --------------------------------------------------------------------------
# Elastic kill-resume: SIGKILL a 4-device sweep, resume on 2 and on 1.
# --------------------------------------------------------------------------
_ELASTIC_SCRIPT = """
import os
import sys
os.environ["XLA_FLAGS"] = \
    "--xla_force_host_platform_device_count=" + sys.argv[4]
import numpy as np
from repro.core.cpd import cp_als
from repro.core.distributed import build_sharded_flycoo
from repro.launch.mesh import make_mesh

dims = (24, 18, 12)
rng = np.random.default_rng(0)
idx = np.unique(np.stack([rng.integers(0, d, 600) for d in dims], 1)
                .astype(np.int32), axis=0)
val = rng.standard_normal(len(idx)).astype(np.float32)
# the tensor is always the 4-device build: its kappas (multiples of 4)
# divide every smaller mesh, which is what makes the restart elastic
t = build_sharded_flycoo(idx, val, dims, n_dev=4, rows_pp=4, block_p=8)
mesh = make_mesh((int(sys.argv[4]),), ("data",))
r = cp_als(t, rank=4, iters=6, mesh=mesh, checkpoint=sys.argv[1],
           resume=(sys.argv[2] == "resume"))
np.savez(sys.argv[3], *[np.asarray(f) for f in r.factors],
         lam=np.asarray(r.lam), fits=np.asarray(r.fits))
"""


def _run_elastic(ckpt_dir, out, mode, devices, chaos_env=None, timeout=900):
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("REPRO_CHAOS", None)
    if chaos_env:
        env["REPRO_CHAOS"] = chaos_env
    return subprocess.run(
        [sys.executable, "-c", _ELASTIC_SCRIPT, ckpt_dir, mode, out,
         str(devices)],
        env=env, capture_output=True, text=True, timeout=timeout)


def test_elastic_kill_resume_across_device_counts(tmp_path):
    """The ISSUE-10 acceptance scenario: a 4-device distributed run is
    SIGKILLed mid-sweep; resuming from its sharded snapshots on 2 devices
    AND on 1 device replays the remaining sweeps bitwise-identically to
    an uninterrupted 4-device run."""
    import shutil
    import signal as _signal

    ckpt = str(tmp_path / "ckpt")
    clean = str(tmp_path / "clean.npz")
    # uninterrupted 4-device reference
    r = _run_elastic(str(tmp_path / "unused"), clean, "fresh", 4)
    assert r.returncode == 0, r.stderr
    # SIGKILL at the start of sweep 3 on 4 devices
    r = _run_elastic(ckpt, "/dev/null", "fresh", 4,
                     chaos_env="kill_sweep=3")
    assert r.returncode == -_signal.SIGKILL, (r.returncode, r.stderr)
    assert os.listdir(ckpt), "no snapshot survived the kill"
    with np.load(clean) as a:
        ref = {name: a[name] for name in a.files}
    for n_dev in (2, 1):
        ckpt_n = str(tmp_path / f"ckpt{n_dev}")
        shutil.copytree(ckpt, ckpt_n)
        out = str(tmp_path / f"resumed{n_dev}.npz")
        r = _run_elastic(ckpt_n, out, "resume", n_dev)
        assert r.returncode == 0, r.stderr
        with np.load(out) as b:
            for name in ref:
                np.testing.assert_array_equal(
                    ref[name], b[name],
                    err_msg=f"{name} (resumed on {n_dev} devices)")


def test_elastic_checkpoint_reshard():
    """Save on a 4-device mesh, restore onto 2 devices (elastic shrink)."""
    out = run_sub("""
        import tempfile
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import configs, sharding as shlib
        from repro.launch.mesh import make_mesh
        from repro.training import (CheckpointManager, OptimizerConfig,
                                    init_state)
        from repro.launch import specs as speclib

        cfg = configs.smoke("olmo-1b")
        ocfg = OptimizerConfig()
        tmp = tempfile.mkdtemp()

        mesh4 = make_mesh((2, 2), ("data", "model"))
        ctx4 = shlib.make_ctx(mesh4)
        state = init_state(cfg, ocfg, jax.random.PRNGKey(0))
        sh4 = speclib.state_shardings(
            jax.eval_shape(lambda: state), ctx4)
        state4 = jax.tree.map(jax.device_put, state, sh4)
        mgr = CheckpointManager(tmp, async_save=False)
        mgr.save(state4, {"step": 0})

        # "restart" on a smaller mesh: 2 devices
        mesh2 = make_mesh((2, 1), ("data", "model"))
        ctx2 = shlib.make_ctx(mesh2)
        sh2 = speclib.state_shardings(jax.eval_shape(lambda: state), ctx2)
        restored, _ = mgr.restore_latest(like=state, shardings=sh2)
        chk = jax.tree.map(
            lambda a, b: bool(jnp.all(a == b)), state, restored)
        assert all(jax.tree.leaves(chk))
        d = jax.tree.leaves(restored)[5]
        assert len(d.sharding.device_set) <= 2
        print("ELASTIC_OK")
    """)
    assert "ELASTIC_OK" in out
