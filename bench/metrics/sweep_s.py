"""Wall seconds per ALS sweep over the window: all of its time, the
per-start ``engine.init`` and fit syncs included, over every sweep it
completed."""


def read(run):
    return (run.window_close - run.window_open) / run.sweeps
