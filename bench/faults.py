"""Faults planted under the timed path, to read the numbers of
``bench.check`` on a run that is known to be wrong. Each is a context
manager that swaps one entry point of ``repro.engine`` while it is open.

* ``state_unchanged``: every sweep hands back the factors it was given;
* ``half_batch``: every layout holds half of the nonzeros, the other half
  scaled by 2 so that the mean stays;
* ``answer_altered``: one entry of the last mode's factor is moved by 0.1
  where the sweep produces it.
"""
from __future__ import annotations

import contextlib
import dataclasses


@contextlib.contextmanager
def _swap(name: str, make):
    import repro.engine as engine

    orig = getattr(engine, name)
    setattr(engine, name, make(orig))
    try:
        yield
    finally:
        setattr(engine, name, orig)


def state_unchanged():
    def make(orig):
        def sweep(state, factors, *, fold=None, carry=None):
            outs, st, _, _ = orig(state, factors, fold=fold, carry=carry)
            return outs, st, list(factors), carry
        return sweep
    return _swap("all_modes", make)


def half_batch():
    def make(orig):
        def init(tensor, config=None, *a, **k):
            v = tensor.values.copy()
            v[::2] = 0.0
            v[1::2] *= 2.0
            return orig(dataclasses.replace(tensor, values=v), config,
                        *a, **k)
        return init
    return _swap("init", make)


def answer_altered():
    def make(orig):
        def sweep(state, factors, *, fold=None, carry=None):
            outs, st, f, c = orig(state, factors, fold=fold, carry=carry)
            f = list(f)
            f[-1] = f[-1].at[0, 0].add(0.1)
            return outs, st, f, c
        return sweep
    return _swap("all_modes", make)


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "answer_altered": answer_altered}
