"""Host seconds of ``build_flycoo`` (the per-mode FLYCOO plans) in set-up."""


def read(run):
    return run.setup["plan_s"]
