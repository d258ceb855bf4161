"""The comparison that decides ``correct``.

A checked start is the program's ``cp_als`` result for one start of the
window, ``(factors, lam, fits)``. The reference (``bench.reference``)
reads it in two ways, and the numbers compared are:

* ``residual``: how far the final state is from what its own last ALS
  update gives (``reference.last_update``): the backward error of that
  update's solve, entry by entry, over the bound that rounding of the
  MTTKRP's terms and of the product allows. It reads the last mode's EC
  kernel, the remap chain that built that mode's layout and the ALS
  update, free of the rounding the earlier sweeps carried in;
* ``fit_gap``: ``|fit|`` of the program's last sweep against the fit of
  its final state as the reference computes it;
* ``factor_gap``: largest relative Frobenius gap
  ``||Y_program - Y_reference|| / ||Y_reference||`` over the modes, where
  the reference reruns the whole start from the same key: it reads every
  mode's kernel and every sweep;
* ``resilience_events``: degradations, recoveries and retries the program
  recorded (each is a departure from the configured path).

Each cell's limits sit in ``bench/limits/<cell>.json``, with the readings
they were set from.
"""
from __future__ import annotations

import json
import os

import numpy as np

from bench import reference

NUMBERS = ("residual", "fit_gap", "factor_gap")


def compare(prog, idx, val, norm_x_sq: float, dims, rank: int, key,
            sweeps: int) -> dict[str, float]:
    """Numbers for the program's start ``prog = (factors, lam, fits)``
    from ``key``, over the device COO ``(idx, val)``."""
    pf, plam, pfits = prog
    pf = [np.asarray(p, np.float32) for p in pf]
    if [p.shape for p in pf] != [(d, rank) for d in dims] \
            or np.shape(plam) != (rank,) or len(pfits) != sweeps:
        return dict.fromkeys(NUMBERS, float("inf"))
    residual, fit = reference.last_update(idx, val, norm_x_sq, pf, plam)
    rf, _, _ = reference.cp_als(idx, val, norm_x_sq, dims, rank, key,
                                sweeps)
    factor_gap = max(
        float(np.linalg.norm(p.astype(np.float64) - r)
              / max(np.linalg.norm(r.astype(np.float64)), 1e-30))
        for p, r in zip(pf, rf))
    nums = {"residual": residual, "fit_gap": abs(float(pfits[-1]) - fit),
            "factor_gap": factor_gap}
    # NaN compares false against any limit; report it as infinite.
    return {k: (v if np.isfinite(v) else float("inf"))
            for k, v in nums.items()}


def load_limits(root: str, cell: str) -> dict[str, float]:
    with open(os.path.join(root, "bench", "limits", f"{cell}.json")) as f:
        return {k: float(v) for k, v in json.load(f)["limits"].items()}


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``(correct, {name: {"value", "limit"}})`` over the limited
    numbers."""
    checks = {k: {"value": numbers[k], "limit": limits[k]}
              for k in limits}
    return all(c["value"] <= c["limit"] for c in checks.values()), checks
