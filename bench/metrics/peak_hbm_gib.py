"""Allocator high-water mark of the fullest chip, in GiB, read right after
the window."""


def read(run):
    return run.peak_bytes / 2 ** 30
