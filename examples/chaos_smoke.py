"""Seeded fault-injection smoke on a tiny zipf tensor (CI chaos-smoke).

Every resilience path runs against a deterministic chaos plan and is
gated on the same two invariants the design promises:

* **Bitwise parity** wherever the ladder claims it — transient upload
  failures retried, a streamed-chunk OOM answered by budget halving, a
  compile failure answered by the backend ladder, and a SIGKILL mid-run
  answered by checkpoint/resume (subprocess, ``REPRO_CHAOS``) must all
  end in factors bitwise-identical to an undisturbed run.
* **No silent degradation** — ``obs.resilience_report()`` must pair every
  injected fault with the resilience event that answered it
  (``unanswered == []``).

Writes ``out/chaos_trace.json`` (Chrome trace of the whole run, chaos
injection spans included) and ``out/chaos_report.json`` (the pairing
report) for the CI artifact.

    PYTHONPATH=src python examples/chaos_smoke.py

``--mesh`` runs the distributed tier instead (requires >= 4 devices —
CI forces fake CPU devices with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``): the exchange
rung (``collective_permute -> all_gather``), device loss -> mesh shrink,
transient dist dispatch retry, and the elastic kill/resume scenario — a
4-device run SIGKILLed mid-sweep and resumed on 2 devices and on 1,
gated bitwise against an uninterrupted 4-device run. Writes
``out/dist_chaos_trace.json`` + ``out/dist_chaos_report.json``.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        PYTHONPATH=src python examples/chaos_smoke.py --mesh
"""
import argparse
import json
import os
import signal
import subprocess
import sys

import numpy as np

from repro import obs
from repro.core.cpd import cp_als
from repro.core.datasets import zipf_tensor
from repro.core.plancache import PlanCache
from repro.engine import ExecutionConfig, PlanSpec, make_engine
from repro.engine.stream import StreamState, cp_als_stream
from repro.resilience import (ChaosSpec, LadderPolicy, chaos, install,
                              uninstall)

DIMS, NNZ, SEED = (60, 50, 40), 3000, 7
RANK, ITERS = 4, 6
POLICY = LadderPolicy(backoff_base_s=1e-4, backoff_cap_s=1e-3)


def _tensor():
    return zipf_tensor(DIMS, NNZ, a=2.0, seed=SEED, rows_pp=8)


def _stream_config():
    return ExecutionConfig(rows_pp=8, chunk_nnz=1024, rank_hint=RANK)


def _bitwise(label, a, b):
    for i, (x, y) in enumerate(zip(a.factors, b.factors)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f"{label}: factor {i}")
    np.testing.assert_array_equal(np.asarray(a.lam), np.asarray(b.lam),
                                  err_msg=f"{label}: lam")
    print(f"  [ok] {label}: bitwise parity")


# --------------------------------------------------------------------------
# Child entry: one ALS run in its own process (the kill/resume scenario).
# --------------------------------------------------------------------------
def child_run(ckpt_dir: str, out_npz: str, resume: bool) -> None:
    t = _tensor()
    r = cp_als(t, rank=RANK, iters=ITERS, checkpoint=ckpt_dir,
               resume=resume)
    np.savez(out_npz, *[np.asarray(f) for f in r.factors],
             lam=np.asarray(r.lam))


def _spawn(ckpt_dir, out_npz, *, resume=False, chaos_env=None):
    env = dict(os.environ)
    env.pop(chaos.ENV_VAR, None)
    if chaos_env:
        env[chaos.ENV_VAR] = chaos_env
    cmd = [sys.executable, os.path.abspath(__file__), "--child",
           ckpt_dir, out_npz] + (["--resume"] if resume else [])
    return subprocess.run(cmd, env=env, capture_output=True, text=True)


def scenario_kill_resume(out_dir: str) -> None:
    print("scenario: SIGKILL at sweep 3 -> resume from snapshot")
    ckpt = os.path.join(out_dir, "chaos_ckpt")
    clean = os.path.join(out_dir, "clean.npz")
    resumed = os.path.join(out_dir, "resumed.npz")
    r = _spawn(os.path.join(out_dir, "ckpt_unused"), clean)
    assert r.returncode == 0, r.stderr
    r = _spawn(ckpt, os.path.join(out_dir, "dead.npz"),
               chaos_env=f"kill_sweep=3,seed={SEED}")
    assert r.returncode == -signal.SIGKILL, (
        f"chaos child should die by SIGKILL, got {r.returncode}\n"
        f"{r.stderr}")
    assert os.listdir(ckpt), "no snapshot survived the kill"
    r = _spawn(ckpt, resumed, resume=True)
    assert r.returncode == 0, r.stderr
    with np.load(clean) as a, np.load(resumed) as b:
        for name in a.files:
            np.testing.assert_array_equal(
                a[name], b[name],
                err_msg=f"kill/resume: {name} diverged")
    print("  [ok] killed + resumed == uninterrupted (bitwise)")


# --------------------------------------------------------------------------
# In-process scenarios.
# --------------------------------------------------------------------------
def scenario_stream_faults(out_dir: str, clean) -> None:
    print("scenario: transient upload failure + chunk OOM (streamed)")
    t = _tensor()
    install(ChaosSpec(upload_fail=1, upload_fail_times=2, oom_chunk=3,
                      seed=SEED))
    res = cp_als_stream(t, rank=RANK, iters=ITERS,
                        config=_stream_config(), ladder=POLICY,
                        checkpoint=os.path.join(out_dir, "stream_ckpt"))
    uninstall()
    _bitwise("retry + budget-halving", clean, res)


def scenario_backend_ladder(clean_resident) -> None:
    print("scenario: compile failure -> backend ladder")
    t = _tensor()
    install(ChaosSpec(compile_fail=("pallas_fused", "pallas"), seed=SEED))
    res = cp_als(t, rank=RANK, iters=ITERS,
                 config=ExecutionConfig(backend="pallas_fused"),
                 ladder=True)
    uninstall()
    _bitwise("pallas_fused -> pallas -> xla", clean_resident, res)


def scenario_nan_recovery() -> None:
    print("scenario: NaN burst -> rollback + ridge recovery")
    t = _tensor()
    install(ChaosSpec(nan_sweep=1, seed=SEED))
    res = cp_als(t, rank=RANK, iters=ITERS, ladder=True)
    uninstall()
    assert all(np.isfinite(np.asarray(f)).all() for f in res.factors)
    assert np.isfinite(res.fits).all(), "fit never recovered from the burst"
    print(f"  [ok] recovered; final fit {res.fits[-1]:.4f}")


def scenario_corrupt_blob(out_dir: str) -> None:
    print("scenario: torn plan-cache blob -> quarantine + self-heal")
    cache_dir = os.path.join(out_dir, "chaos_plancache")
    t = _tensor()
    idx, val = np.asarray(t.indices), np.asarray(t.values)
    install(ChaosSpec(corrupt_blob=True, seed=SEED))
    PlanCache(path=cache_dir).get_tensor(idx, val, t.dims, rows_pp=8)
    uninstall()
    healer = PlanCache(path=cache_dir)
    healer.get_tensor(idx, val, t.dims, rows_pp=8)
    assert healer.stats()["disk_corrupt"] == 1, "torn blob not detected"
    reader = PlanCache(path=cache_dir)
    reader.get_tensor(idx, val, t.dims, rows_pp=8)
    assert reader.stats()["disk_loads"] == 1, "cache did not self-heal"
    print("  [ok] quarantined + rebuilt + re-persisted")


def scenario_resident_oom() -> None:
    print("scenario: resident placement OOM -> streaming fallback")
    t = _tensor()
    install(ChaosSpec(oom_resident=True, seed=SEED))
    state = make_engine(t, PlanSpec(chunk_nnz=1024, rank_hint=RANK),
                       ladder=True)
    uninstall()
    assert isinstance(state, StreamState), "factory did not fall back"
    print("  [ok] fell back to the out-of-core tier")


# --------------------------------------------------------------------------
# Distributed tier (--mesh): dist rungs + elastic kill/resume.
# --------------------------------------------------------------------------
def _dist_tensor():
    from repro.core.distributed import build_sharded_flycoo

    t = _tensor()
    return build_sharded_flycoo(np.asarray(t.indices),
                                np.asarray(t.values), t.dims, n_dev=4,
                                rows_pp=8, block_p=8)


def child_run_mesh(ckpt_dir: str, out_npz: str, n_dev: int,
                   resume: bool) -> None:
    from repro.launch.mesh import make_mesh

    t = _dist_tensor()
    r = cp_als(t, rank=RANK, iters=ITERS, mesh=make_mesh((n_dev,),
                                                         ("data",)),
               checkpoint=ckpt_dir, resume=resume)
    np.savez(out_npz, *[np.asarray(f) for f in r.factors],
             lam=np.asarray(r.lam))


def _spawn_mesh(ckpt_dir, out_npz, n_dev, *, resume=False, chaos_env=None):
    env = dict(os.environ)
    env.pop(chaos.ENV_VAR, None)
    if chaos_env:
        env[chaos.ENV_VAR] = chaos_env
    # each child picks its own device count BEFORE importing jax
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    cmd = [sys.executable, os.path.abspath(__file__), "--child-mesh",
           ckpt_dir, out_npz, str(n_dev)] + (["--resume"] if resume else [])
    return subprocess.run(cmd, env=env, capture_output=True, text=True)


def scenario_dist_exchange(clean) -> None:
    from repro.launch.mesh import make_mesh

    print("scenario: exchange failure -> permute -> all_gather rung")
    install(ChaosSpec(exchange_fail=1, seed=SEED))
    res = cp_als(_dist_tensor(), rank=RANK, iters=ITERS,
                 mesh=make_mesh((4,), ("data",)), ladder=POLICY)
    uninstall()
    _bitwise("permute -> all_gather", clean, res)


def scenario_dist_device_loss(out_dir: str, clean) -> None:
    from repro.launch.mesh import make_mesh

    print("scenario: device loss -> mesh shrink 4 -> 2 from snapshot")
    install(ChaosSpec(device_lost=2, device_lost_n=2, seed=SEED))
    res = cp_als(_dist_tensor(), rank=RANK, iters=ITERS,
                 mesh=make_mesh((4,), ("data",)), ladder=POLICY,
                 checkpoint=os.path.join(out_dir, "dist_ckpt"))
    uninstall()
    _bitwise("mesh shrink 4->2", clean, res)
    degr = obs.REGISTRY.metrics()["resilience_degradations"].as_dict()
    assert degr.get("device_lost:4->2", 0) >= 1, degr


def scenario_dist_transient(clean) -> None:
    from repro.launch.mesh import make_mesh

    print("scenario: transient dist dispatch -> retry with backoff")
    install(ChaosSpec(dist_transient=1, dist_transient_times=2, seed=SEED))
    res = cp_als(_dist_tensor(), rank=RANK, iters=ITERS,
                 mesh=make_mesh((4,), ("data",)), ladder=POLICY)
    uninstall()
    _bitwise("dist dispatch retry", clean, res)


def scenario_elastic_kill_resume(out_dir: str) -> None:
    import shutil

    print("scenario: SIGKILL a 4-device sweep -> resume on 2 and on 1")
    ckpt = os.path.join(out_dir, "elastic_ckpt")
    clean = os.path.join(out_dir, "dist_clean.npz")
    r = _spawn_mesh(os.path.join(out_dir, "elastic_unused"), clean, 4)
    assert r.returncode == 0, r.stderr
    r = _spawn_mesh(ckpt, os.path.join(out_dir, "dist_dead.npz"), 4,
                    chaos_env=f"kill_sweep=3,seed={SEED}")
    assert r.returncode == -signal.SIGKILL, (
        f"chaos child should die by SIGKILL, got {r.returncode}\n"
        f"{r.stderr}")
    assert os.listdir(ckpt), "no sharded snapshot survived the kill"
    for n_dev in (2, 1):
        ckpt_n = os.path.join(out_dir, f"elastic_ckpt{n_dev}")
        shutil.rmtree(ckpt_n, ignore_errors=True)
        shutil.copytree(ckpt, ckpt_n)
        resumed = os.path.join(out_dir, f"dist_resumed{n_dev}.npz")
        r = _spawn_mesh(ckpt_n, resumed, n_dev, resume=True)
        assert r.returncode == 0, r.stderr
        with np.load(clean) as a, np.load(resumed) as b:
            for name in a.files:
                np.testing.assert_array_equal(
                    a[name], b[name],
                    err_msg=f"elastic resume on {n_dev} dev: {name}")
        print(f"  [ok] resumed on {n_dev} device(s) == uninterrupted "
              "4-device run (bitwise)")


def main_mesh(out_dir: str) -> None:
    import jax

    from repro.launch.mesh import make_mesh

    n = len(jax.devices())
    assert n >= 4, (
        f"--mesh needs >= 4 devices, found {n}; run under "
        "XLA_FLAGS=--xla_force_host_platform_device_count=4")
    obs.enable()
    uninstall()

    t = _dist_tensor()
    print(f"zipf tensor dims={DIMS} nnz={t.values.size} (4-device build)")
    clean = cp_als(t, rank=RANK, iters=ITERS,
                   mesh=make_mesh((4,), ("data",)))

    scenario_dist_exchange(clean)
    scenario_dist_device_loss(out_dir, clean)
    scenario_dist_transient(clean)
    scenario_elastic_kill_resume(out_dir)

    report = obs.resilience_report()
    with open(os.path.join(out_dir, "dist_chaos_report.json"), "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
    obs.write_chrome_trace(os.path.join(out_dir, "dist_chaos_trace.json"))
    print("\nresilience pairing (dist):")
    for site in sorted(report["injections"]):
        mark = "answered" if site in report["answered"] else "UNANSWERED"
        print(f"  {site:<14} x{report['injections'][site]:<3} {mark}")
    assert report["unanswered"] == [], (
        f"silent degradation: {report['unanswered']}")
    print("\nall dist chaos scenarios answered; wrote "
          f"{out_dir}/dist_chaos_trace.json + "
          f"{out_dir}/dist_chaos_report.json")


def _require_cpu_backend() -> None:
    """The scenarios run JAX here and then start child processes that run
    JAX too. An accelerator serves one process at a time, so a parent
    holding one would block its children: refuse anything but the CPU."""
    import jax

    backend = jax.default_backend()
    if backend != "cpu":
        sys.exit(f"chaos_smoke runs JAX in this process and in child "
                 f"processes; the {backend} backend serves one process at "
                 "a time. Rerun with JAX_PLATFORMS=cpu.")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", nargs=2, metavar=("CKPT", "OUT"),
                    help="internal: run one ALS child process")
    ap.add_argument("--child-mesh", nargs=3, metavar=("CKPT", "OUT", "NDEV"),
                    help="internal: run one distributed ALS child process")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--mesh", action="store_true",
                    help="run the distributed chaos scenarios")
    ap.add_argument("--out", default="out")
    args = ap.parse_args()
    if args.child:
        child_run(args.child[0], args.child[1], args.resume)
        return
    if args.child_mesh:
        child_run_mesh(args.child_mesh[0], args.child_mesh[1],
                       int(args.child_mesh[2]), args.resume)
        return
    _require_cpu_backend()
    if args.mesh:
        os.makedirs(args.out, exist_ok=True)
        main_mesh(args.out)
        return

    os.makedirs(args.out, exist_ok=True)
    obs.enable()
    uninstall()                      # a stray REPRO_CHAOS must not leak in

    t = _tensor()
    print(f"zipf tensor dims={DIMS} nnz={t.values.size}")
    clean_stream = cp_als_stream(t, rank=RANK, iters=ITERS,
                                 config=_stream_config())
    clean_resident = cp_als(t, rank=RANK, iters=ITERS,
                            config=ExecutionConfig(backend="xla"))

    scenario_stream_faults(args.out, clean_stream)
    scenario_backend_ladder(clean_resident)
    scenario_nan_recovery()
    scenario_corrupt_blob(args.out)
    scenario_resident_oom()
    scenario_kill_resume(args.out)

    report = obs.resilience_report()
    with open(os.path.join(args.out, "chaos_report.json"), "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
    obs.write_chrome_trace(os.path.join(args.out, "chaos_trace.json"))
    print("\nresilience pairing:")
    for site in sorted(report["injections"]):
        mark = "answered" if site in report["answered"] else "UNANSWERED"
        print(f"  {site:<14} x{report['injections'][site]:<3} {mark}")
    assert report["unanswered"] == [], (
        f"silent degradation: {report['unanswered']}")
    print("\nall chaos scenarios answered; wrote "
          f"{args.out}/chaos_trace.json + {args.out}/chaos_report.json")


if __name__ == "__main__":
    main()
