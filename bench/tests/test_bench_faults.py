"""A whole run of the harness on the CPU (no look for a chip, kernels in
interpret mode) with the timed path broken underneath: every fault must
turn ``correct`` false, and the unbroken run must stay true."""
import jax
import pytest

from bench import faults, run, spec


def _run(root, monkeypatch, trace=False):
    monkeypatch.setattr(run, "peak_bytes", lambda devices: 1)
    cell = spec.resolve(root, "tiny.als_r8")
    return run.run_cell(root, cell, 2 ** 33 + 5, 0.0, trace, jax.devices())


def test_sound_run_is_correct(tiny_root, monkeypatch):
    out = _run(tiny_root, monkeypatch)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"setup_s", "sweep_s", "peak_hbm_gib"}
    assert list(out)[-1] == "checks"


def test_traced_run_reports_per_layer_metrics(tiny_root, monkeypatch):
    out = _run(tiny_root, monkeypatch, trace=True)
    assert out["correct"], out["checks"]
    # the host's metrics; the CPU has no TPU plane, so the device's
    # readers find nothing and are left out of the line
    assert set(out["metrics"]) == {"plan_s", "init_s", "start_init_s"}
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_fault_turns_correct_false(tiny_root, monkeypatch, fault):
    with faults.FAULTS[fault]():
        out = _run(tiny_root, monkeypatch)
    assert not out["correct"], out["checks"]
