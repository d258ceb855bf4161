"""repro.obs acceptance surface (ISSUE 8).

Span nesting/ordering and attrs, thread-safety of concurrent spans,
the disabled-mode no-op fast path (bounded overhead), Chrome-trace
schema round-trip + validation, metrics-registry parity with the
legacy engine counters, the span-derived vs count-derived streaming
``overlap_efficiency`` agreement on a real streamed ``cp_als``, the
``memory_probe`` relocation, and ``time_fn``'s dispersion stats.
"""
import json
import threading
import time

import numpy as np
import pytest

import jax

import repro.engine as engine
from repro import obs
from repro.core.flycoo import build_flycoo
from repro.obs.trace import SpanRecord


@pytest.fixture
def tracer():
    """A private tracer installed as the global one for the test."""
    prev = obs.get_tracer()
    t = obs.enable(obs.Tracer(xla_annotations=False))
    try:
        yield t
    finally:
        if prev is None:
            obs.disable()
        else:
            obs.enable(prev)


@pytest.fixture
def registry():
    """A private registry (the global one stays untouched)."""
    return obs.MetricsRegistry()


def _coo(nnz=900, seed=0, dims=(29, 23, 19)):
    rng = np.random.default_rng(seed)
    idx = np.unique(
        np.stack([rng.integers(0, d, nnz) for d in dims], 1)
        .astype(np.int64), axis=0)
    return idx, rng.standard_normal(len(idx)).astype(np.float32), dims


# --------------------------------------------------------------------------
# Spans: nesting, ordering, attrs.
# --------------------------------------------------------------------------
def test_span_nesting_and_ordering(tracer):
    with obs.span("outer", who="a"):
        with obs.span("inner1"):
            pass
        with obs.span("inner2") as sp:
            sp.set("late", 42)
    spans = tracer.spans()
    assert [s.name for s in spans] == ["outer", "inner1", "inner2"]
    outer, inner1, inner2 = spans
    assert outer.parent_id is None
    assert inner1.parent_id == outer.span_id
    assert inner2.parent_id == outer.span_id
    assert outer.attrs == {"who": "a"}
    assert inner2.attrs == {"late": 42}
    # wall-clock containment
    assert outer.start_ns <= inner1.start_ns <= inner1.end_ns
    assert inner2.end_ns <= outer.end_ns
    assert inner1.end_ns <= inner2.start_ns  # sequential siblings


def test_traced_decorator(tracer):
    @obs.traced("my.fn", tag=1)
    def f(x):
        return x + 1

    assert f(1) == 2
    (s,) = tracer.spans()
    assert s.name == "my.fn" and s.attrs == {"tag": 1}


def test_span_survives_exception(tracer):
    with pytest.raises(RuntimeError):
        with obs.span("boom"):
            raise RuntimeError("x")
    (s,) = tracer.spans()
    assert s.name == "boom"
    # the stack popped: a new root span has no parent
    with obs.span("after"):
        pass
    assert tracer.spans()[1].parent_id is None


def test_thread_safety(tracer):
    def work(i):
        for j in range(50):
            with obs.span("t", worker=i):
                with obs.span("t.child"):
                    pass

    threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    spans = tracer.spans()
    assert len(spans) == 4 * 50 * 2
    # every child's parent is a span on ITS OWN thread
    by_id = {s.span_id: s for s in spans}
    for s in spans:
        if s.name == "t.child":
            assert by_id[s.parent_id].thread_id == s.thread_id


def test_disabled_is_noop_and_cheap():
    prev = obs.get_tracer()
    obs.disable()
    try:
        assert not obs.is_enabled()
        sp = obs.span("x", a=1)
        assert sp is obs.NULL_SPAN
        with sp:
            sp.set("k", "v")
        # bounded overhead: a disabled span costs within 50x of a bare
        # no-op context (both are nanoseconds; 50x keeps CI noise out)
        n = 20_000

        class _Bare:
            def __enter__(self):
                return self

            def __exit__(self, *e):
                return False

        bare = _Bare()

        def loop_bare():
            t0 = time.perf_counter()
            for _ in range(n):
                with bare:
                    pass
            return time.perf_counter() - t0

        def loop_span():
            t0 = time.perf_counter()
            for _ in range(n):
                with obs.span("x"):
                    pass
            return time.perf_counter() - t0

        loop_bare(), loop_span()  # warm
        t_bare = min(loop_bare() for _ in range(3))
        t_span = min(loop_span() for _ in range(3))
        assert t_span < max(t_bare * 50, 20e-3), (t_span, t_bare)
    finally:
        if prev is not None:
            obs.enable(prev)


# --------------------------------------------------------------------------
# Metrics registry.
# --------------------------------------------------------------------------
def test_counter_dict_surface(registry):
    c = registry.counter("c", "help")
    c.inc("a")
    c["a"] += 2          # legacy dict-style increment
    c["b"] = 5
    assert c["a"] == 3 and c["b"] == 5 and c["missing"] == 0
    assert dict(c) == {"a": 3, "b": 5}
    assert set(c.keys()) == {"a", "b"}
    assert c.total() == 8
    c.clear()
    assert dict(c) == {} and c["a"] == 0


def test_gauge_and_histogram(registry):
    g = registry.gauge("g")
    g.set("x", 1.5)
    g.max("x", 0.5)      # running max keeps 1.5
    g.max("x", 2.5)
    assert g["x"] == 2.5
    h = registry.histogram("h")
    for v in (1.0, 3.0, 2.0):
        h.observe("k", v)
    s = h.summary("k")
    assert s["count"] == 3 and s["min"] == 1.0 and s["max"] == 3.0
    assert s["mean"] == pytest.approx(2.0)


def test_registry_kind_conflict(registry):
    registry.counter("m")
    with pytest.raises(TypeError):
        registry.gauge("m")


def test_legacy_counter_parity():
    """TRACE_COUNTS / DISPATCH_COUNTS live on the obs registry but keep
    the legacy surface the benchmarks and tests rely on."""
    assert isinstance(engine.TRACE_COUNTS, obs.Counter)
    assert engine.TRACE_COUNTS is obs.REGISTRY.counter("engine_traces")
    engine.reset_counters()
    idx, val, dims = _coo()
    t = build_flycoo(idx, val, dims)
    state = engine.init(t, engine.ExecutionConfig(backend="xla"))
    factors = [jax.random.uniform(k, (d, 4), jax.numpy.float32)
               for k, d in zip(jax.random.split(jax.random.PRNGKey(0),
                                                len(dims)), dims)]
    for _ in range(3):
        outs, state = engine.all_modes(state, factors)
    assert engine.DISPATCH_COUNTS["all_modes"] == 3
    assert engine.TRACE_COUNTS["all_modes"] == 1
    assert dict(engine.DISPATCH_COUNTS)["all_modes"] == 3
    # and the same numbers flow out through the registry snapshot
    snap = {m["name"]: m["values"] for m in obs.REGISTRY.collect()}
    assert snap["engine_dispatches"]["all_modes"] == 3
    engine.reset_counters()
    assert engine.DISPATCH_COUNTS["all_modes"] == 0


# --------------------------------------------------------------------------
# Export: Chrome-trace schema round-trip.
# --------------------------------------------------------------------------
def test_chrome_trace_roundtrip(tracer, registry, tmp_path):
    registry.counter("events").inc("n", 7)
    with obs.span("parent", mode=1):
        with obs.span("child", chunk=0):
            pass
    path = tmp_path / "trace.json"
    obs.write_chrome_trace(str(path), tracer, registry,
                           manifest={"test": True})
    with open(path) as f:
        trace = json.load(f)
    assert obs.validate_chrome_trace(trace) == []
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"parent", "child"}
    child = next(e for e in xs if e["name"] == "child")
    parent = next(e for e in xs if e["name"] == "parent")
    assert child["args"]["parent_id"] == parent["args"]["span_id"]
    assert child["args"]["chunk"] == 0
    assert child["ts"] >= parent["ts"] >= 0
    cs = [e for e in trace["traceEvents"] if e["ph"] == "C"]
    assert any(e["name"] == "events" and e["args"] == {"n": 7} for e in cs)
    assert trace["metadata"]["manifest"] == {"test": True}
    assert trace["metadata"]["span_count"] == 2


def test_validate_rejects_malformed():
    assert obs.validate_chrome_trace([]) != []
    assert obs.validate_chrome_trace({"traceEvents": "nope"}) != []
    bad = {"traceEvents": [{"name": "x", "ph": "X", "pid": 0, "tid": 0,
                            "ts": -1, "dur": 1, "args": {}}]}
    errs = obs.validate_chrome_trace(bad)
    assert any("ts" in e for e in errs)
    assert any("span_id" in e for e in errs)


def test_env_var_enables(tmp_path):
    import subprocess
    import sys
    out = tmp_path / "t.json"
    code = ("import repro.obs as o\n"
            "assert o.is_enabled()\n"
            "with o.span('x'):\n"
            "    pass\n")
    subprocess.run([sys.executable, "-c", code], check=True,
                   env={"PYTHONPATH": "src", "REPRO_TRACE": str(out),
                        "PATH": "/usr/bin:/bin"}, cwd="/root/repo")
    trace = json.loads(out.read_text())
    assert obs.validate_chrome_trace(trace) == []
    assert any(e.get("name") == "x" for e in trace["traceEvents"])


# --------------------------------------------------------------------------
# The spans of a CPD-ALS start that the chip benchmark reads.
# --------------------------------------------------------------------------
def _flycoo():
    idx, val, dims = _coo()
    return build_flycoo(idx.astype(np.int32), val, dims, rows_pp=4,
                        block_p=8)


def test_engine_upload_span_after_init_with_its_bytes(tracer):
    t = _flycoo()
    state = engine.init(t, engine.ExecutionConfig(backend="pallas_fused",
                                                  interpret=True))
    spans = {s.name: s for s in tracer.spans()}
    init, up = spans["engine.init"], spans["engine.upload"]
    assert up.parent_id is None and up.start_ns >= init.end_ns
    # every array device_place hands over: each mode's pinned (val, lrow),
    # the relabel tables and each mode's schedule and dedup tables
    assert state.layout == init.attrs["layout"] == "pinned"
    expect = sum(8 * p.padded_nnz for p in t.plans)
    expect += sum(4 * p.row_relabel.size for p in t.plans)
    for d, p in enumerate(t.plans):
        uidx, upos, nuniq = t.dedup_tables(d)
        expect += 4 * (p.block_part.size + uidx.size + upos.size
                       + nuniq.size)
    assert up.attrs["bytes"] == expect


def test_engine_upload_waits_only_while_tracing(monkeypatch):
    waited = []
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: waited.append(x) or x)
    t = _flycoo()
    cfg = engine.ExecutionConfig(backend="xla")
    prev = obs.disable()
    try:
        engine.init(t, cfg)
        assert waited == []
        tracer = obs.enable(obs.Tracer(xla_annotations=False))
        state = engine.init(t, cfg)
    finally:
        obs.disable()
        if prev is not None:
            obs.enable(prev)
    assert len(waited) == 1 and waited[0] is state
    assert [s.name for s in tracer.spans()].count("engine.upload") == 1


def test_cpd_start_and_fit_spans_nest(tracer):
    from repro.core import cp_als

    res = cp_als(_flycoo(), 4, iters=3,
                 config=engine.ExecutionConfig(backend="xla"))
    spans = tracer.spans()
    (start,) = [s for s in spans if s.name == "cpd.start"]
    assert start.parent_id is None
    for name in ("engine.init", "engine.upload"):
        (s,) = [s for s in spans if s.name == name]
        assert s.parent_id == start.span_id
    sweeps = [s for s in spans if s.name == "cpd.sweep"]
    fits = [s for s in spans if s.name == "cpd.fit"]
    assert len(sweeps) == len(fits) == 3
    assert start.end_ns <= sweeps[0].start_ns
    for sweep, fit, value in zip(sweeps, fits, res.fits):
        assert fit.parent_id == sweep.span_id
        assert sweep.attrs["fit"] == value
        (dispatch,) = [s for s in spans if s.name == "engine.dispatch"
                       and s.parent_id == sweep.span_id]
        assert dispatch.end_ns <= fit.start_ns <= fit.end_ns <= sweep.end_ns


# --------------------------------------------------------------------------
# Span-derived vs count-derived streaming overlap.
# --------------------------------------------------------------------------
def test_overlap_rule_synthetic():
    mk = lambda name, sid, par, t0, t1, **a: SpanRecord(
        name, sid, par, 1, "main", t0, t1, a)
    spans = [
        mk("stream.mode", 1, None, 0, 100),
        mk("stream.upload", 2, 1, 1, 4, chunk=0),   # first: never overlapped
        mk("stream.upload", 3, 1, 5, 9, chunk=1),   # prefetch before c0 runs
        mk("stream.compute", 4, 1, 10, 30, chunk=0),
        mk("stream.compute", 5, 1, 31, 50, chunk=1),
    ]
    assert obs.stream_overlap_from_spans(spans) == 0.5
    # same via a chrome export
    t = obs.Tracer(xla_annotations=False)
    for s in spans:
        t._record(s)
    trace = obs.chrome_trace(t, obs.MetricsRegistry())
    assert obs.stream_overlap_from_chrome(trace) == 0.5
    assert obs.stream_overlap_from_spans([]) is None


def test_streamed_cpd_overlap_agreement(tracer):
    """The ISSUE 8 acceptance: on a streamed cp_als run the span-derived
    overlap_efficiency agrees with StreamStats' upload-count metric
    within 0.1 (they are in fact constructed to agree exactly)."""
    from repro.engine.stream import cp_als_stream, stream_init

    idx, val, dims = _coo(nnz=2000)
    t = build_flycoo(idx, val, dims, kappa=4)
    config = engine.ExecutionConfig(backend="xla", kappa_policy="fixed",
                                    kappa=4, chunk_nnz=128, stream_ring=2)
    state = stream_init(t, config)
    assert state.plan.chunks[0].nchunks > 1, "need multiple chunks"
    res = cp_als_stream(t, rank=4, iters=2, config=config)
    assert len(res.fits) == 2

    span_eff = obs.stream_overlap_from_spans(tracer.spans())
    # count-derived, via a fresh run's StreamStats (same plan/config)
    state2 = stream_init(t, config)
    factors = [jax.random.uniform(k, (d, 4), jax.numpy.float32)
               for k, d in zip(jax.random.split(jax.random.PRNGKey(1),
                                                len(dims)), dims)]
    from repro.engine.stream import stream_all_modes
    stream_all_modes(state2, factors)
    count_eff = state2.stats.overlap_efficiency
    assert state2.stats.uploads > 0 and count_eff > 0
    assert span_eff is not None
    assert abs(span_eff - count_eff) <= 0.1, (span_eff, count_eff)


def test_stream_stats_as_row_has_device_peak():
    from repro.engine.stream import StreamStats

    row = StreamStats().as_row()
    assert "device_peak_bytes" in row  # None on CPU jax is fine


# --------------------------------------------------------------------------
# Report.
# --------------------------------------------------------------------------
def test_render_report(tracer, registry):
    registry.counter("plan_cache_outcomes").inc("hit", 3)
    registry.counter("plan_cache_outcomes").inc("miss")
    with obs.span("factory.make_engine"):
        with obs.span("plan.mode", mode=0):
            pass
    text = obs.render_report(tracer, registry)
    assert "factory.make_engine" in text and "plan.mode" in text
    assert "hit" in text and "75.0%" in text
    md = obs.render_report(tracer, registry, fmt="markdown")
    assert md.startswith("# repro run report")
    with pytest.raises(ValueError):
        obs.render_report(tracer, registry, fmt="html")


# --------------------------------------------------------------------------
# Satellites: probe relocation + time_fn dispersion.
# --------------------------------------------------------------------------
def test_memory_probe_moved_and_reexported():
    import benchmarks.common as common

    assert common.memory_probe is obs.memory_probe
    probe = obs.memory_probe()
    assert probe["host_peak_rss_bytes"] is None or \
        probe["host_peak_rss_bytes"] > 0


def test_time_fn_dispersion(tmp_path, monkeypatch):
    import benchmarks.common as common

    t = common.time_fn(lambda: np.zeros(4), iters=5, warmup=1)
    assert isinstance(t, common.Timing) and float(t) > 0
    assert set(t.stats) == {"p10", "p90", "iqr", "timing_iters"}
    assert t.stats["p10"] <= float(t) <= t.stats["p90"]
    us = t * 1e6           # the figure scripts' unit conversion
    assert isinstance(us, common.Timing)
    assert us.stats["p90"] == pytest.approx(t.stats["p90"] * 1e6)
    assert us.stats["timing_iters"] == 5
    # emit folds the stats into the JSON extras
    out = tmp_path / "results.json"
    monkeypatch.setattr(common, "_JSON_PATH", str(out))
    common.emit([("row", us, 1.0)])
    rec = {r["name"]: r for r in json.loads(out.read_text())}["row"]
    assert rec["p90"] == pytest.approx(round(t.stats["p90"] * 1e6, 1))
    assert rec["timing_iters"] == 5
