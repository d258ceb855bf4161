"""The control, the reference at the next lower precision in the
program's place, must fail the limits of each cell's configuration at a
size a test run holds (on the chip it is read at the cell's own size by
``bench/calibrate.py --control``)."""
import jax
import numpy as np
import pytest

from bench import check, gen, reference, run, spec
from bench.tests.conftest import REPO


@pytest.mark.parametrize("cell", ["uber.als_r32"])
def test_control_is_not_correct(cell):
    c = spec.resolve(REPO, cell)
    dims, rank = tuple(c.config["dims"]), int(c.traffic["rank"])
    sweeps = int(c.config["sweeps_per_start"])
    indices, values = gen.tensor(dict(c.config, nnz=30000), 7)
    idx, val = reference.device_coo(indices, values)
    norm_x_sq = float(np.sum(values.astype(np.float64) ** 2))
    key = run.start_key(jax, 7, 0)
    args = (idx, val, norm_x_sq, dims, rank, key, sweeps)
    ctl = reference.cp_als(*args, precision="high")
    numbers = check.compare(ctl, *args)
    numbers["resilience_events"] = 0
    correct, checks = check.judge(numbers, check.load_limits(REPO, cell))
    assert not correct, checks
