"""Set-up seconds: from the first statement of ``bench/run.py`` to the
opening of the window (COO, plans, the first ``engine.init``, compile and
the warm-up start)."""


def read(run):
    return run.window_open - run.t0
