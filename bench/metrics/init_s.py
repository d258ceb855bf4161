"""Host seconds of the first ``engine.init`` in set-up (dedup tables, host
layout, upload), to ``block_until_ready``."""


def read(run):
    return run.setup["init_s"]
