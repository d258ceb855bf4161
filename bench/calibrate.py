#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from (``bench/limits``).

    python3 bench/calibrate.py --workload uber.als_r32 --seeds 1,2,3 --control

For each seed, in one process: the cell's tensor is drawn and planned as
``bench/run.py`` does, one ``cp_als`` start runs as the window runs it
(key ``fold_in(key(seed), 0)``), and ``bench.check.compare`` reads the
program against the reference. With ``--control`` the control (the
reference at the next lower precision, ``bench.reference``) is read in the
program's place; with ``--faults`` so is the program with each fault of
``bench.faults`` planted. One JSON line per seed.

``--trace-dir DIR`` runs a second start of the first seed under the
profiler, with the program's spans on, and keeps the ``.xplane.pb`` under
``DIR``.
``--config FILE --rank R`` runs a configuration file that no cell names.
Runs only on a TPU.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import check, faults, reference, run, spec, tracereduce  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--config", help="configuration file, with --rank")
    ap.add_argument("--rank", type=int)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", action="store_true",
                    help="also read every fault of bench.faults")
    ap.add_argument("--trace-dir")
    args = ap.parse_args(argv)
    if args.workload:
        cell = spec.resolve(ROOT, args.workload)
        conf, rank = cell.config, int(cell.traffic["rank"])
    else:
        with open(args.config) as f:
            conf = json.load(f)
        rank = args.rank
    sys.path.insert(0, os.path.join(ROOT, "src"))
    jax = run.setup_jax()
    try:
        devices = run.tpu_devices(jax, 1)
    except run.NoChip as exc:
        print(f"FAIL: {exc}", file=sys.stderr)
        return 3
    import numpy as np

    from repro.core import cp_als
    from repro.obs import trace as obs_trace

    cfg = run.engine_config(conf)
    fault_names = sorted(faults.FAULTS) if args.faults else []
    sweeps = int(conf["sweeps_per_start"])
    dims = tuple(int(d) for d in conf["dims"])
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        setup: dict = {}
        tensor = run.make_tensor(conf, seed, cfg, setup)
        key = run.start_key(jax, seed, 0)
        t = time.monotonic()
        res = cp_als(tensor, rank, iters=sweeps, key=key, config=cfg,
                     ladder=False)
        jax.block_until_ready(res.factors)
        start_s = time.monotonic() - t
        if args.trace_dir and n == 0:   # a second start, compiled, traced
            from jax.profiler import ProfileOptions

            opts = ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            tracer = obs_trace.enable(obs_trace.Tracer())
            jax.profiler.start_trace(args.trace_dir, profiler_options=opts)
            with jax.profiler.TraceAnnotation(tracereduce.WINDOW_SPAN):
                with jax.profiler.TraceAnnotation("bench.start"):
                    res = cp_als(tensor, rank, iters=sweeps, key=key,
                                 config=cfg, ladder=False)
                    jax.block_until_ready(res.factors)
            jax.profiler.stop_trace()
            spans = [(s.name, s.duration_ns) for s in tracer.spans()]
            obs_trace.disable()
            print(json.dumps({"seed": seed, "obs_spans": spans[:200]}),
                  flush=True)
        peak = run.peak_bytes(devices)
        prog = ([np.asarray(f) for f in res.factors], np.asarray(res.lam),
                list(res.fits))
        del res
        idx, val = reference.device_coo(tensor.indices, tensor.values)
        norm_x_sq = float(np.sum(tensor.values.astype(np.float64) ** 2))
        ref_args = (idx, val, norm_x_sq, dims, rank, key, sweeps)
        t = time.monotonic()
        line = {"seed": seed, "nnz": tensor.nnz, "setup": setup,
                "start_s": start_s,
                "peak_bytes": peak, "fits": prog[2],
                "program": check.compare(prog, *ref_args)}
        line["check_s"] = time.monotonic() - t
        if args.control:
            ctl = reference.cp_als(*ref_args, precision="high")
            line["control"] = check.compare(ctl, *ref_args)
        for name in fault_names:
            with faults.FAULTS[name]():
                res = cp_als(tensor, rank, iters=sweeps, key=key,
                             config=cfg, ladder=False)
                bad = ([np.asarray(f) for f in res.factors],
                       np.asarray(res.lam), list(res.fits))
            line[name] = check.compare(bad, *ref_args)
        line["resilience_events"] = run.resilience_events()
        del idx, val, tensor
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
