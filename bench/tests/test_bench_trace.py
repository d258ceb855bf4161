import os

import numpy as np
import pytest

from bench import tracereduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
DEV0, DEV1 = "/device:TPU:0", "/device:TPU:1"


def _ev(name, a, b, where=DEV0):
    return tr.Event(name, float(a), float(b - a), where)


@pytest.fixture
def synthetic():
    ops = (
        _ev("a", 0, 30), _ev("b", 20, 50), _ev("a", 70, 80),
        _ev("c", 95, 130),                     # crosses the window's end
        _ev("a", 10, 40, DEV1),
    )
    spans = (
        tr.Event("bench.window", 5, 115),      # window [5, 120]
        tr.Event("bench.start", 5, 115),
        tr.Event("engine.init", 50, 20),       # [50, 70]
        tr.Event("engine.host_layout", 52, 10),
        tr.Event("engine.device_place", 62, 3),
    )
    return tr.Trace(ops=ops, spans=spans)


def test_window_union_and_busy(synthetic):
    lo, hi = tr.window(synthetic)
    assert (lo, hi) == (5.0, 120.0)
    assert tr.union([(0, 30), (20, 50), (60, 61), (61, 62)]) == \
        [(0, 50), (60, 62)]
    # chip 0: [5, 50] + [70, 80] + [95, 120] = 45 + 10 + 25 = 80
    # chip 1: [10, 40] = 30; mean 55
    assert tr.busy_ns(synthetic, lo, hi) == pytest.approx(55.0)


def test_gaps_are_named_by_the_span_overlapping_most(synthetic):
    lo, hi = tr.window(synthetic)
    assert tr.gaps(synthetic, lo, hi) == [(50, 70), (80, 95)]
    # [50, 70]: engine.init covers all of it and host_layout half: the
    # innermost that covers half wins; device_place covers too little.
    assert tr.name_gap(synthetic, 50, 70) == "engine.host_layout"
    # [80, 95]: only bench.start overlaps.
    assert tr.name_gap(synthetic, 80, 95) == "bench.start"
    # [58, 70]: host_layout and device_place cover less than half.
    assert tr.name_gap(synthetic, 58, 70) == "engine.init"
    # [112, 130]: no span covers half; the one that overlaps most wins.
    assert tr.name_gap(synthetic, 112, 130) == "bench.start"
    assert tr.name_gap(synthetic, 200, 300) == "(no span)"


def test_breakdown_and_op_seconds(synthetic):
    lo, hi = tr.window(synthetic)
    got = tr.breakdown(synthetic, lo, hi)
    # a: 25 + 10 (chip 0) + 30 (chip 1) = 65 ns; b: 30 ns; c: 25 ns
    assert got["device_ops"] == [["a", pytest.approx(65e-9)],
                                 ["b", pytest.approx(30e-9)],
                                 ["c", pytest.approx(25e-9)]]
    assert got["idle_gaps"] == [["engine.host_layout", pytest.approx(20e-9)],
                                ["bench.start", pytest.approx(15e-9)]]
    assert tr.op_seconds(synthetic, lo, hi, lambda e: e.name == "a") == \
        pytest.approx(65e-9)


def test_a_window_must_be_there_once():
    with pytest.raises(ValueError):
        tr.window(tr.Trace(ops=(), spans=()))


@pytest.fixture(scope="module")
def chip_trace():
    """Two ALS sweeps of uber's dims at 20k nonzeros on one TPU v5e
    (``bench/calibrate.py --config bench/tests/data/small_config.json``)."""
    return tr.load(os.path.join(DATA, "small_trace.xplane.pb"))


def test_recorded_chip_trace_names(chip_trace):
    lo, hi = tr.window(chip_trace)
    assert chip_trace.devices == (DEV0,)
    kernels = [e for e in chip_trace.ops if tr.is_ec_kernel(e)]
    remaps = [e for e in chip_trace.ops if tr.is_remap(e, 4)]
    # one kernel and one slot-record scatter per mode and sweep
    assert len(kernels) == 8 and len(remaps) == 8
    assert {e.op.rsplit(".", 1)[0] for e in kernels} == \
        {"%mttkrp_fused_gather_compact"}
    assert not [e for e in chip_trace.ops if tr.is_remap(e, 5)]
    assert not [e for e in chip_trace.ops if " while(" in e.name]
    # read by hand from the trace
    assert tr.op_seconds(chip_trace, lo, hi, tr.is_ec_kernel) == \
        pytest.approx(4.97422e-3, rel=1e-5)
    assert tr.op_seconds(chip_trace, lo, hi, lambda e: tr.is_remap(e, 4)) \
        == pytest.approx(6.42876e-4, rel=1e-5)


def test_recorded_chip_trace_busy_and_gaps(chip_trace):
    lo, hi = tr.window(chip_trace)
    # busy by a raster of 10 ns cells, independent of the interval union
    cells = np.zeros(int((hi - lo) / 10) + 1, dtype=bool)
    for e in chip_trace.ops:
        a, b = max(e.start_ns, lo), min(e.end_ns, hi)
        if b > a:
            cells[int((a - lo) / 10):int((b - lo) / 10)] = True
    busy = tr.busy_ns(chip_trace, lo, hi)
    assert busy == pytest.approx(cells.sum() * 10, rel=0.01)
    gaps = tr.gaps(chip_trace, lo, hi)
    assert sum(b - a for a, b in gaps) == pytest.approx(hi - lo - busy)
    out = tr.breakdown(chip_trace, lo, hi)
    assert len(out["device_ops"]) == 10 and len(out["idle_gaps"]) == 10
    assert out["device_ops"][0][0].startswith("%mttkrp")
    # the longest gap is each start's engine.init, inside its tables' upload
    assert out["idle_gaps"][0][0].startswith("engine.")
