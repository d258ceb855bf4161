import numpy as np
import pytest

from bench import gen
from repro.core import datasets


def _keys(idx, dims):
    return idx.astype(np.int64) @ gen.linear_strides(dims)


@pytest.mark.parametrize("dims,nnz,seed", [
    ((40, 30, 8, 6), 3000, 0),
    ((183, 24, 1140, 1717), 20000, 2 ** 40 + 7),
    ((1654, 114, 2, 100, 89), 26000, 5),
])
def test_generator_matches_datasets_synthesize(dims, nnz, seed):
    """The first batch is ``datasets.synthesize``'s draw: every coordinate
    it makes is kept, and the stream tops it up to ``nnz`` distinct."""
    ts = datasets.TensorSpec(name="t", dims=dims, nnz=nnz, zipf_a=1.2)
    want, _ = datasets.synthesize(ts, seed=seed)
    idx = gen.coordinates(dims, nnz, 1.2, seed)
    assert idx.dtype == want.dtype and idx.shape == (nnz, len(dims))
    assert want.shape[0] < nnz
    assert np.all(np.isin(_keys(want, dims), _keys(idx, dims)))


def test_generator_rows_distinct_and_in_range():
    dims = (183, 24, 1140, 1717)
    idx = gen.coordinates(dims, 5000, 1.2, 3)
    assert np.all(np.diff(_keys(idx, dims)) > 0)
    assert np.all(idx >= 0) and np.all(idx < np.asarray(dims))


def test_generator_keeps_the_skew_of_one_draw():
    """Topping up draws through the same permutations: the hottest index
    of every mode is the one datasets.synthesize makes hottest."""
    dims, nnz = (183, 24, 1140, 1717), 20000
    ts = datasets.TensorSpec(name="t", dims=dims, nnz=nnz, zipf_a=1.2)
    want, _ = datasets.synthesize(ts, seed=0)
    idx = gen.coordinates(dims, nnz, 1.2, 0)
    for d, dim in enumerate(dims):
        assert np.argmax(np.bincount(idx[:, d], minlength=dim)) == \
            np.argmax(np.bincount(want[:, d], minlength=dim))


def test_linear_strides_refuse_an_index_space_over_int64():
    assert list(gen.linear_strides((2, 3, 4))) == [12, 4, 1]
    with pytest.raises(ValueError):
        gen.linear_strides((2 ** 32, 2 ** 32))
    with pytest.raises(ValueError):
        gen.coordinates((2, 3), 7, 1.2)


def test_every_seed_has_the_same_sizes():
    from repro.core import build_flycoo

    conf = {"dims": [183, 24, 1140, 1717], "nnz": 20000, "zipf_a": 1.2}
    (ia, va), (ib, vb) = gen.tensor(conf, 5), gen.tensor(conf, 2 ** 40 + 9)
    assert ia.shape == ib.shape == (20000, 4) and not np.array_equal(va, vb)
    assert not np.array_equal(ia, ib)
    for d, dim in enumerate(conf["dims"]):
        assert sorted(np.bincount(ia[:, d], minlength=dim)) == \
            sorted(np.bincount(ib[:, d], minlength=dim))
    plans = [[p.nblocks for p in build_flycoo(i, v, conf["dims"]).plans]
             for i, v in ((ia, va), (ib, vb))]
    assert plans[0] == plans[1]
    np.testing.assert_array_equal(gen.tensor(conf, 5)[0], ia)
