"""The readers of the program's own spans, scopes and counters, on
synthetic traces: ``start_upload_s``, the scope readers of
``bench.opscope`` and ``ec_ns_per_row_copy``."""
import pytest

from bench import opscope, run, spec
from bench import tracereduce as tr
from bench.tests.conftest import REPO
from repro.engine import api
from repro.obs.metrics import REGISTRY
from repro.obs.trace import SpanRecord

MS = 1e6                 # ns
DEV = "/device:TPU:0"
SCOPES = {
    "%fusion.1 = s32[8,9]{1,0}": api.OpScope(0, "remap", frozenset({"ec"})),
    "%mttkrp_fused_gather_compact.4 = f32[8,128]{1,0}": api.OpScope(0, "ec"),
    "%fusion.2 = f32[4,4]{1,0}": api.OpScope(1, "fold"),
}


def _op(text, a, b):
    return tr.Event(text, a * MS, (b - a) * MS, DEV)


def _run(ops=(), spans=(), obs_spans=(), sweeps=1):
    trace = tr.Trace(ops=tuple(ops), spans=(tr.Event(
        tr.WINDOW_SPAN, 0.0, 10 * MS), *spans))
    return run.Run(t0=0.0, setup={}, window_open=0.0, window_close=1.0,
                   starts=1, sweeps=sweeps, nnz=100, dims=(8, 6, 4, 4),
                   rank=8, device_kind="TPU v5 lite", peak_bytes=1,
                   spans=tuple(obs_spans), trace=trace,
                   trace_window=tr.window(trace))


def _sweep_ops():
    """remap 3 ms, ec 2 ms, fold 0.5 ms and 0.6 ms in no scope: an op of
    another program under a name of the map, and one cut by the window."""
    return (
        _op("%fusion.1 = s32[8,9]{1,0} fusion(s32[8,9]{1,0} %p), "
            "kind=kCustom", 0, 3),
        _op("%fusion.1 = f32[4]{0} fusion(f32[4]{0} %q), kind=kLoop", 3, 3.5),
        _op("%mttkrp_fused_gather_compact.4 = f32[8,128]{1,0} custom-call()",
            4, 6),
        _op("%fusion.2 = f32[4,4]{1,0} fusion(f32[4,4]{1,0} %r)", 6, 6.5),
        _op("%fusion.3 = f32[2]{0} fusion()", 9.9, 11),
    )


@pytest.fixture
def scopes(monkeypatch):
    calls = []
    monkeypatch.setattr(opscope, "program_scopes",
                        lambda: calls.append(1) or SCOPES)
    return calls


def test_start_upload_s_is_the_mean_upload_of_the_window(capsys):
    up = [SpanRecord("engine.upload", i, None, 1, "main", 0, 1,
                     {"bytes": 4_000_000}) for i in (1, 2)]
    spans = (
        tr.Event("engine.upload", 1 * MS, 0.4 * MS),
        tr.Event("engine.upload", 6 * MS, 0.6 * MS),
        tr.Event("engine.upload", 12 * MS, 5 * MS),    # after the window
    )
    reader = spec.load_reader(REPO, "start_upload_s")
    got = reader(_run(ops=_sweep_ops(), spans=spans, obs_spans=up))
    assert got == pytest.approx(0.5e-3)
    assert "4000000 bytes in 0.0005 s, 8 GB/s" in capsys.readouterr().out
    assert reader(_run(ops=_sweep_ops())) is None
    # no device plane (a run on the CPU): nothing went to a device
    assert reader(_run(spans=spans, obs_spans=up)) is None


@pytest.mark.parametrize("name,ms", [("remap_scope_ms", 3.0 / 2),
                                     ("fold_ms", 0.5 / 2),
                                     ("unscoped_ms", 0.6 / 2)])
def test_scope_readers(scopes, name, ms):
    assert spec.load_reader(REPO, name)(_run(_sweep_ops(), sweeps=2)) == \
        pytest.approx(ms)


def test_scopes_add_up_to_the_busy_time_and_are_read_once(scopes, capsys):
    r = _run(_sweep_ops(), sweeps=2)
    got = [spec.load_reader(REPO, name)(r) for name in
           ("remap_scope_ms", "fold_ms", "unscoped_ms")]
    ms = opscope.per_sweep_ms(r)
    assert ms["ec"] == pytest.approx(1.0)
    lo, hi = r.trace_window
    assert sum(got) + ms["ec"] == pytest.approx(
        tr.busy_ns(r.trace, lo, hi) * 1e-6 / r.sweeps)
    assert len(scopes) == 1
    out = capsys.readouterr().out
    # the remap fusion also holds EC ops, at 1.5 ms a sweep
    assert "%fusion.1 (1.5 ms a sweep) is charged to mode 0 remap and " \
        "also holds ops of ec" in out


@pytest.mark.parametrize("name", ["remap_scope_ms", "fold_ms",
                                  "unscoped_ms", "ec_ns_per_row_copy"])
def test_a_program_without_scopes_or_counter_reads_nothing(monkeypatch,
                                                           name):
    """What a parent without the named scopes, ``op_scopes`` and the
    row-copy gauge gives: nothing, and no error."""
    monkeypatch.delattr(api, "op_scopes")
    monkeypatch.setattr(REGISTRY, "metrics", lambda: {})
    assert spec.load_reader(REPO, name)(_run(_sweep_ops())) is None


def test_no_device_ops_or_an_empty_map_read_nothing(monkeypatch):
    monkeypatch.setattr(opscope, "program_scopes", lambda: {})
    assert opscope.per_sweep_ms(_run(_sweep_ops())) is None
    monkeypatch.setattr(opscope, "program_scopes", lambda: SCOPES)
    assert opscope.per_sweep_ms(_run()) is None


def test_ec_ns_per_row_copy(capsys):
    gauge = REGISTRY.gauge("engine_row_copies")
    saved = gauge.as_dict()
    try:
        gauge.clear()
        for d, copies in enumerate((100, 200, 300, 400, 999)):
            gauge.set(d, copies)        # mode 4 is another tensor's
        got = spec.load_reader(REPO, "ec_ns_per_row_copy")(
            _run(_sweep_ops(), sweeps=2))
    finally:
        gauge.clear()
        for k, v in saved.items():
            gauge.set(k, v)
    assert got == pytest.approx(2 * MS / 2 / 1000)
    assert "over 1000 row copies a sweep" in capsys.readouterr().out
