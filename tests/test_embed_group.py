"""Group embedding of subset answers against the per-clique loop it replaced.

``embed_group`` fills each subset-position region of a whole signature group
with one block copy; the reference below embeds one clique at a time into
float64 zeros, stacks the rows and casts them, as reconstruction used to.
Every value is copied, none computed, so the two must agree bit for bit.
"""
import itertools
from types import SimpleNamespace

import numpy as np
import pytest

from repro.core import Domain
from repro.core.mechanism import Measurement, signature_groups
from repro.core.reconstruct import embed_group, embed_subset_answers


def _reference(measurements, group, sizes, dtype):
    rows = []
    for c in group:
        t = np.zeros(sizes)
        for r in range(len(c) + 1):
            for sub in itertools.combinations(c, r):
                region = tuple(slice(0, n - 1) if i in sub else slice(n - 1, n)
                               for i, n in zip(c, sizes))
                shape = tuple(n - 1 if i in sub else 1
                              for i, n in zip(c, sizes))
                t[region] = np.asarray(measurements[sub].omega,
                                       dtype=np.float64).reshape(shape)
        rows.append(t.reshape(-1))
    return np.stack(rows).astype(dtype)


def _measurements(domain, cliques, omega_dtype, seed=0):
    rng = np.random.default_rng(seed)
    closure = {s for c in cliques for r in range(len(c) + 1)
               for s in itertools.combinations(c, r)}
    return {s: Measurement(s, rng.standard_normal(domain.residual_size(s))
                           .astype(omega_dtype), 1.0)
            for s in closure}


def _kway(n_attrs, k):
    return list(itertools.combinations(range(n_attrs), k))


CASES = {
    # Synth-like 10^k signatures; every pair / triple shares its subsets.
    "synth_k0": ([10] * 3, [()]),
    "synth_k1": ([10] * 5, _kway(5, 1)),
    "synth_k2": ([10] * 5, _kway(5, 2)),
    "synth_k3": ([10] * 6, _kway(6, 3)),
    # Adult-like mixed sizes, 2-valued axes (one measured slot) and size 100:
    # every signature group of the ≤3-way workload.
    "adult_mixed": ([100, 2, 100, 2, 7, 9],
                    [c for k in range(4) for c in _kway(6, k)]),
    # Three triples that pairwise share a 2-way subset and all share (0,).
    "shared_subsets": ([10] * 4, [(0, 1, 2), (0, 1, 3), (0, 2, 3)]),
}


@pytest.mark.parametrize("out_dtype", [np.float32, np.float64])
@pytest.mark.parametrize("omega_dtype", [np.float32, np.float64])
@pytest.mark.parametrize("case", sorted(CASES))
def test_embed_group_bit_equal_to_per_clique_loop(case, omega_dtype,
                                                  out_dtype):
    sizes_all, cliques = CASES[case]
    dom = Domain.create(sizes_all)
    meas = _measurements(dom, cliques, omega_dtype)
    groups = signature_groups(dom, cliques)
    for sizes, group in groups.items():
        got = embed_group(meas, group, sizes, out_dtype)
        want = _reference(meas, group, sizes, out_dtype)
        assert got.dtype == want.dtype and got.shape == want.shape
        assert np.array_equal(got, want), (case, sizes)


@pytest.mark.parametrize("case", ["synth_k0", "synth_k3", "adult_mixed"])
def test_embed_subset_answers_is_one_row_of_the_group(case):
    """The single-clique embedding keeps its float64 default and N-D shape."""
    sizes_all, cliques = CASES[case]
    dom = Domain.create(sizes_all)
    meas = _measurements(dom, cliques, np.float32)
    plan = SimpleNamespace(domain=dom)
    for c in cliques:
        sizes = dom.clique_sizes(c)
        t = embed_subset_answers(plan, meas, c)
        assert t.dtype == np.float64 and t.shape == sizes
        assert np.array_equal(t.reshape(1, -1),
                              _reference(meas, [c], sizes, np.float64))
