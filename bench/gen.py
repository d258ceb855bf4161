"""Seeded COO generator for the benchmark's sparse tensors.

:func:`coordinates` draws coordinates as ``repro.core.datasets.synthesize``
does (Zipf ranks, permuted over each mode) and keeps drawing, with the
same permutations, until ``nnz`` distinct coordinates exist: the tensor
holds the configuration's published nonzero count, not the fewer distinct
rows that one batch of draws leaves. Its first batch is the draw of
``datasets.synthesize``, kept here so that the program cannot change the
yardstick's inputs. It deduplicates on a 1-D linear key instead of
``np.unique(axis=0)``: the linear key of a row orders rows exactly as the
lexicographic row sort does, and sorting int64 keys is far faster.

:func:`tensor` is what a run draws. The coordinates come from
``STRUCTURE_SEED``; the run's seed relabels the rows of every mode and
draws the values. So every seed gives a tensor with the same nonzero count
and the same degrees per mode, in another order: the program's plans,
block counts and compiled programs are the same for every seed, and only
the first run in a checkout compiles.
"""
from __future__ import annotations

import numpy as np

#: Seed of every configuration's coordinates.
STRUCTURE_SEED = 0


def linear_strides(dims) -> np.ndarray:
    """Row-major strides of ``dims`` as int64; raises where the index space
    does not fit a signed 64-bit key."""
    if np.prod([float(d) for d in dims]) >= 2.0 ** 63:
        raise ValueError(f"index space of {tuple(dims)} exceeds int64 keys")
    strides = np.ones(len(dims), dtype=np.int64)
    for d in range(len(dims) - 2, -1, -1):
        strides[d] = strides[d + 1] * int(dims[d + 1])
    return strides


def _draw_keys(rng: np.random.Generator, dims, n: int, zipf_a: float,
               perms: list) -> np.ndarray:
    """Linear keys of ``n`` drawn coordinates. Each mode's permutation is
    drawn after its first ranks, as ``datasets.synthesize`` does, and kept
    in ``perms`` for the later batches."""
    strides = linear_strides(dims)
    key = np.zeros(n, dtype=np.int64)
    for d, dim in enumerate(dims):
        raw = rng.zipf(zipf_a, size=n)
        if perms[d] is None:
            perms[d] = rng.permutation(int(dim))  # rank apart from index id
        key += perms[d][(raw - 1) % int(dim)].astype(np.int64) * strides[d]
    return key


def coordinates(dims, nnz: int, zipf_a: float,
                seed: int = STRUCTURE_SEED) -> np.ndarray:
    """The first ``nnz`` distinct coordinates of a stream of Zipf draws
    from ``seed``, as rows ``(nnz, N)`` int32 in lexicographic order."""
    if nnz > np.prod([float(d) for d in dims]):
        raise ValueError(f"{nnz} distinct coordinates exceed {tuple(dims)}")
    rng = np.random.default_rng(seed)
    perms: list = [None] * len(dims)
    keys, n = np.empty(0, dtype=np.int64), nnz
    while keys.size < nnz:
        drawn = _draw_keys(rng, dims, n, zipf_a, perms)
        _, first = np.unique(drawn, return_index=True)
        new = drawn[np.sort(first)]              # distinct, in draw order
        new = new[~np.isin(new, keys)]
        keys = np.concatenate([keys, new[:nnz - keys.size]])
        # the next batch, from this batch's share of new coordinates
        n = int((nnz - keys.size) * n / max(new.size, 1) * 1.25) + 1024
    keys.sort()
    strides = linear_strides(dims)
    indices = np.empty((nnz, len(dims)), dtype=np.int32)
    for d, dim in enumerate(dims):
        indices[:, d] = (keys // strides[d]) % int(dim)
    return indices


def tensor(conf: dict, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """The COO of configuration ``conf`` for the run seed ``seed``."""
    indices = coordinates(conf["dims"], conf["nnz"], conf["zipf_a"])
    rng = np.random.default_rng(seed)
    for d, dim in enumerate(conf["dims"]):
        indices[:, d] = rng.permutation(int(dim)).astype(np.int32)[
            indices[:, d]]
    values = rng.standard_normal(indices.shape[0]).astype(np.float32)
    return indices, values
