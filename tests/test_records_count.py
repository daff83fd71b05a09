"""The records -> tables count (``engine/sharded.py:_local_marginals``) is
numpy's bincount, bit for bit.

Cliques of one or two attributes are counted by a one-hot Gram product,
wider cliques by a sum of one-hots of their cell codes, in the same loop
over chunks of rows.  Every case here is exact in its dtype, so any
difference from the bincount is a fault, not rounding.
"""
import itertools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import Domain
from repro.engine import sharded

DOM = Domain.create([4, 3, 5, 2, 3, 7])
N = 1000
CHUNK = 64


@pytest.fixture
def x64():
    was = jax.config.read("jax_enable_x64")
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", was)


def _closure(kmax):
    return [c for k in range(kmax + 1)
            for c in itertools.combinations(range(DOM.n_attrs), k)]


def _metas(cliques):
    return [(c,) + sharded._clique_strides(DOM, c) for c in cliques]


def _bincounts(cliques, recs):
    out = []
    for c in cliques:
        if not c:
            out.append(np.array([recs.shape[0]]))
            continue
        code = np.zeros(recs.shape[0], np.int64)
        for a in c:
            code = code * DOM.sizes[a] + recs[:, a]
        out.append(np.bincount(code, minlength=DOM.n_cells(c)))
    return out


def _count(recs, metas, dtype, n_rows=None):
    return jax.jit(lambda r, n: sharded._local_marginals(r, metas, dtype, n))(
        jnp.asarray(recs), None if n_rows is None else np.int32(n_rows))


def _assert_bincount(got, cliques, recs, dtype):
    assert len(got) == len(cliques)
    for c, g, want in zip(cliques, got, _bincounts(cliques, recs)):
        assert g.dtype == dtype
        assert np.array_equal(np.asarray(g), want), c


@pytest.mark.parametrize("n_rows", [None, 777])
@pytest.mark.parametrize("dtype", ["float32", "float64"])
@pytest.mark.parametrize("kmax", [1, 2, 3])
def test_local_marginals_equal_bincount(monkeypatch, request, kmax, dtype,
                                        n_rows):
    """Closures of width <= 1, 2 and 3 (the empty clique in each; at 3
    product and compare-and-sum cliques together), over 1,000 rows in
    chunks of 64, all of them or the first 777 of the buffer."""
    if dtype == "float64":
        request.getfixturevalue("x64")
    cliques = _closure(kmax)
    metas = _metas(cliques)
    widest = max([sum(DOM.sizes)] + [m[2] for m in metas if len(m[0]) > 2])
    monkeypatch.setattr(sharded, "_ONE_HOT_ELEMS", CHUNK * widest)
    assert sharded._chunk_rows(metas[1:]) == CHUNK
    assert N % CHUNK and 777 % CHUNK
    rng = np.random.default_rng(kmax)
    recs = np.stack([rng.integers(0, s, N) for s in DOM.sizes],
                    axis=1).astype(np.int32)
    got = _count(recs, metas, jnp.dtype(dtype), n_rows)
    _assert_bincount(got, cliques, recs[:n_rows], jnp.dtype(dtype))


@pytest.mark.parametrize("dtype", ["float32", "float64"])
def test_one_cell_of_300000_rows_is_exact(request, dtype):
    """300,000 identical rows in one cell of every table: a count that
    accumulated in bfloat16 (exact to 256) or float16 (to 2,048) would read
    another number."""
    if dtype == "float64":
        request.getfixturevalue("x64")
    cliques = _closure(3)
    recs = np.tile(np.array([[3, 2, 4, 1, 2, 6]], np.int32), (300_000, 1))
    got = _count(recs, _metas(cliques), jnp.dtype(dtype))
    _assert_bincount(got, cliques, recs, jnp.dtype(dtype))
    assert float(got[-1][-1]) == 300_000


@pytest.mark.parametrize("kmax,product", [(0, 0), (1, 6), (2, 21), (3, 21)])
def test_product_counts_one_and_two_attribute_cliques(kmax, product):
    """The Gram product takes every clique of one or two attributes, a
    one-hot block over all their attributes; the empty clique and wider
    ones are counted otherwise."""
    cliques = _closure(kmax)
    store = sharded.RecordStore(DOM, cliques, np.zeros((3, DOM.n_attrs), np.int32))
    assert store.product_cliques == product
    cols, width = sharded._gram_columns(_metas(cliques))
    assert list(cols) == (list(range(DOM.n_attrs)) if kmax else [])
    assert [s for _o, s in cols.values()] == (list(DOM.sizes) if kmax else [])
    assert width == (sum(DOM.sizes) if kmax else 0)
