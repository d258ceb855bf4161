#!/usr/bin/env python3
"""Chip benchmark of FLYCOO CPD-ALS: one run of one cell.

    python3 bench/run.py --workload uber.als_r32 --seed 7 --seconds 51 --trace 0

Run from the root of a checkout on a machine with the TPUs the cell asks
for; without them it exits non-zero and prints no result. A cell is a
configuration (``bench/configs``: the tensor, the ALS sweeps per start and
the engine settings) under a traffic mix (``bench/traffic``: the rank).

Set-up: the COO tensor is drawn from ``--seed`` (``bench.gen.tensor``), planned
(``build_flycoo``), one ``engine.init`` is timed and dropped, and one
``cp_als(iters=1)`` warms up every program the window runs. The window
then runs ``cp_als`` back to back, one start per key
``fold_in(key(seed), i)``, as a multi-start CPD does, and closes at the
first call boundary after ``--seconds``.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` runs the
window under the JAX profiler with the program's spans on, and reports the
per-layer metrics, the device's busy and window seconds and a breakdown.
In both, one start of the window drawn from the seed is checked against
``bench.reference`` after the window (``bench.check``). The last line of
standard output is the result as JSON; the numbers compared, beside their
limits, are the last lines of standard error.
"""
import time

T0 = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import check, gen, reference, spec, tracereduce  # noqa: E402

CACHE_DIR = os.path.join(ROOT, "bench", ".jax_cache")
#: Counters of the program's resilience layer; any event is a departure
#: from the configured path.
RESILIENCE_COUNTERS = ("resilience_degradations", "resilience_recoveries",
                       "resilience_retries")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: Start index of the warm-up's key, apart from the window's 0, 1, ...
WARMUP_START = 2 ** 32 - 1


def log(msg: str) -> None:
    print(msg, flush=True)


class NoChip(RuntimeError):
    pass


@dataclasses.dataclass
class Run:
    """What one run measured; the metric readers take their numbers from
    it (``bench/metrics/<name>.py``)."""

    t0: float
    setup: dict            # set-up phases, host seconds
    window_open: float
    window_close: float
    starts: int
    sweeps: int
    nnz: int
    dims: tuple
    rank: int
    device_kind: str
    peak_bytes: int
    spans: tuple = ()      # repro.obs span records of the window
    trace: object = None   # tracereduce.Trace of the window
    trace_window: tuple = (0.0, 0.0)


def start_key(jax, seed: int, i: int):
    """Key of start ``i``; all 64 bits of the seed count."""
    base = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)
    return jax.random.fold_in(base, i)


def setup_jax():
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def tpu_devices(jax, chips: int):
    """The first ``chips`` TPUs; ``NoChip`` without them."""
    try:
        devices = jax.devices()
    except RuntimeError as exc:
        raise NoChip(f"no TPU found: JAX could not start a backend ({exc})")
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU found: JAX sees {len(devices)} "
                     f"{devices[0].platform} device(s); this benchmark "
                     "runs only on a TPU")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} TPUs, JAX sees "
                     f"{len(devices)}")
    return devices[:chips]


def peak_bytes(devices) -> int:
    """Allocator high-water mark of the fullest chip; an error where the
    backend does not report ``peak_bytes_in_use``."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" not in stats:
            raise RuntimeError(f"{d} reports no peak_bytes_in_use")
        peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks)


def engine_config(conf: dict):
    from repro.engine import ExecutionConfig

    return ExecutionConfig(**conf["engine"])


def make_tensor(conf: dict, seed: int, cfg, setup: dict):
    """The cell's COO from ``seed`` and its FLYCOO plans."""
    from repro.core import build_flycoo

    t = time.monotonic()
    indices, values = gen.tensor(conf, seed)
    setup["coo_s"] = time.monotonic() - t
    t = time.monotonic()
    tensor = build_flycoo(indices, values, conf["dims"],
                          rows_pp=cfg.resolve_rows_pp(), block_p=cfg.block_p,
                          schedule=cfg.schedule)
    setup["plan_s"] = time.monotonic() - t
    return tensor


def resilience_events() -> int:
    from repro.obs.metrics import REGISTRY

    return int(sum(REGISTRY.counter(n).total() for n in RESILIENCE_COUNTERS))


def run_cell(root: str, cell: spec.Cell, seed: int, seconds: float,
             trace: bool, devices, t0: float = T0) -> dict:
    """One run of ``cell`` on ``devices``; returns the result object."""
    import jax

    compiles: list = []

    def on_event(event, secs, **_):
        if event == COMPILE_EVENT:
            compiles.append(secs)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        return _run_cell(root, cell, seed, seconds, trace, devices, t0,
                         compiles)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)


def _run_cell(root, cell, seed, seconds, trace, devices, t0, compiles):
    import jax
    import numpy as np

    from repro import engine
    from repro.core import cp_als
    from repro.obs import trace as obs_trace

    conf, rank = cell.config, int(cell.traffic["rank"])
    sweeps_per_start = int(conf["sweeps_per_start"])
    dims = tuple(int(d) for d in conf["dims"])
    cfg = engine_config(conf)
    setup: dict = {}
    tensor = make_tensor(conf, seed, cfg, setup)
    log(f"tensor {cell.config_name}: dims {dims}, {conf['nnz']} drawn, "
        f"nnz {tensor.nnz} after dedupe; blocks per mode "
        f"{[p.nblocks for p in tensor.plans]}")
    t = time.monotonic()
    jax.block_until_ready(engine.init(tensor, cfg))
    setup["init_s"] = time.monotonic() - t
    t, n_compiles = time.monotonic(), len(compiles)
    res = cp_als(tensor, rank, iters=1,
                 key=start_key(jax, seed, WARMUP_START),
                 config=cfg, ladder=False)
    jax.block_until_ready(res.factors)
    del res
    setup["warmup_s"] = time.monotonic() - t
    setup["compile_s"] = sum(compiles[n_compiles:])
    log("set-up (host s): " + ", ".join(f"{k} {v:.3f}"
                                         for k, v in setup.items()))

    log_dir = tracer = None
    if trace:
        from jax.profiler import ProfileOptions

        log_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1   # annotations only
        tracer = obs_trace.enable(obs_trace.Tracer(xla_annotations=True))
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    n_compiles = len(compiles)
    results = []
    with jax.profiler.TraceAnnotation(tracereduce.WINDOW_SPAN):
        opened = time.monotonic()
        while not results or time.monotonic() - opened < seconds:
            with jax.profiler.TraceAnnotation("bench.start"):
                res = cp_als(tensor, rank, iters=sweeps_per_start,
                             key=start_key(jax, seed, len(results)),
                             config=cfg, ladder=False)
                jax.block_until_ready(res.factors)
            results.append(res)
        closed = time.monotonic()
    window_compiles = len(compiles) - n_compiles
    run = Run(t0=t0, setup=setup, window_open=opened, window_close=closed,
              starts=len(results), sweeps=len(results) * sweeps_per_start,
              nnz=tensor.nnz, dims=dims, rank=rank,
              device_kind=devices[0].device_kind,
              peak_bytes=peak_bytes(devices))
    if trace:
        jax.profiler.stop_trace()
        run.spans = tracer.spans()   # on only for the window
        obs_trace.disable()
        run.trace = tracereduce.load(tracereduce.find_xplane(log_dir))
        run.trace_window = tracereduce.window(run.trace)
        shutil.rmtree(log_dir, ignore_errors=True)
    log(f"window: {run.starts} starts x {sweeps_per_start} sweeps in "
        f"{closed - opened:.3f} s; {window_compiles} compiles inside it; "
        f"peak {run.peak_bytes} bytes")

    failed = sum(not np.all(np.isfinite(r.fits)) for r in results)
    pick = int(np.random.default_rng(seed).integers(len(results)))
    checked = results[pick]
    prog = ([np.asarray(f) for f in checked.factors],
            np.asarray(checked.lam), list(checked.fits))
    del results, checked, res
    t = time.monotonic()
    idx, val = reference.device_coo(tensor.indices, tensor.values)
    norm_x_sq = float(np.sum(tensor.values.astype(np.float64) ** 2))
    numbers = check.compare(prog, idx, val, norm_x_sq, dims, rank,
                            start_key(jax, seed, pick), sweeps_per_start)
    numbers["resilience_events"] = resilience_events()
    del idx, val
    log(f"reference for start {pick}: {time.monotonic() - t:.3f} s; "
        f"program fits {prog[2]}; numbers {json.dumps(numbers)}")
    correct, checks = check.judge(numbers,
                                  check.load_limits(root, cell.name))

    chosen = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in chosen:
        value = m.read(run)
        if value is None:
            if not m.per_layer:
                raise RuntimeError(f"end-to-end metric {m.name} read "
                                   "nothing")
            continue
        metrics[m.name] = {"value": float(value), "unit": m.unit}
    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices), "memory_peak_bytes": run.peak_bytes}
    out = {"correct": bool(correct and failed == 0),
           "attempted": run.starts, "failed": int(failed),
           "metrics": metrics, "device": device}
    if trace:
        lo, hi = run.trace_window
        device["busy_s"] = tracereduce.busy_ns(run.trace, lo, hi) * 1e-9
        device["window_s"] = (hi - lo) * 1e-9
        out["breakdown"] = tracereduce.breakdown(run.trace, lo, hi)
    out["checks"] = checks
    return out


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not 0 <= args.seed < 2 ** 63:
        ap.error("--seed must be a whole number in [0, 2**63)")
    return args


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        cell = spec.resolve(ROOT, args.workload)
    except (OSError, KeyError, ValueError) as exc:
        print(f"FAIL: {exc}", file=sys.stderr)
        return 2
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"FAIL: the program (src/repro) is not in {ROOT}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    jax = setup_jax()
    try:
        devices = tpu_devices(jax, cell.chips)
    except NoChip as exc:
        print(f"FAIL: {exc}", file=sys.stderr)
        return 3
    log(f"device: {devices[0].device_kind} x{len(devices)} "
        f"(jax {jax.__version__}); cell {cell.name}, seed {args.seed}")
    out = run_cell(ROOT, cell, args.seed, args.seconds, bool(args.trace),
                   devices)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
