r"""Reconstruction phase (Algorithm 2) and closed-form variances (Theorem 4).

Reconstruction of the marginal on A uses only the noisy residual answers
ω_{A'} for A' ⊆ A, independently of every other attribute and marginal — the
marginals can therefore be reconstructed in parallel, on demand, and they are
mutually consistent.  The per-axis factors of U_{A←A'} are:

    Sub_{n_i}^†     for i ∈ A'          (Lemma 1 closed form)
    (1/n_i)·1       for i ∈ A \ A'      (column vector)
    [1]             for i ∉ A           (axis absent)
"""
from __future__ import annotations

import math
from functools import lru_cache
from typing import Dict, List, Mapping, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from repro.obs import TRACER

from .domain import Clique, Domain, subsets
from .kron import kron_matvec, kron_matvec_np
from .mechanism import Measurement, host_bytes
from .residual import sub_pinv, variance_coeff
from .select import Plan


def _u_factors(domain: Domain, clique: Clique, sub_clique: Clique):
    """Per-axis factors and input dims of U_{A←A'} restricted to A's axes."""
    sc = set(sub_clique)
    factors, in_dims = [], []
    for i in clique:
        n = domain.attributes[i].size
        if i in sc:
            factors.append(sub_pinv(n))
            in_dims.append(n - 1)
        else:
            factors.append(np.full((n, 1), 1.0 / n))
            in_dims.append(1)
    return factors, in_dims


def u_chain_factors(domain: Domain, clique: Clique) -> List[np.ndarray]:
    """Per-axis factors T_i = [ Sub_{n_i}^† | (1/n_i)·1 ]  (n_i × n_i).

    The key identity behind batched reconstruction (docs/DESIGN.md §5): for
    every A' ⊆ A, U_{A←A'} ω_{A'} equals (⊗_{i∈A} T_i) e_{A'}, where e_{A'}
    embeds ω_{A'} into the (n_i)_{i∈A} tensor at axis-i slots 0..n_i-2 when
    i ∈ A' and slot n_i-1 otherwise.  Distinct subsets occupy *disjoint*
    slot regions, so Algorithm 2's sum over 2^|A| subset matvecs collapses to
    ONE Kronecker chain applied to the sum of embeddings.
    """
    out = []
    for i in clique:
        n = domain.attributes[i].size
        out.append(np.hstack([sub_pinv(n), np.full((n, 1), 1.0 / n)]))
    return out


def subset_slot_region(clique: Clique, sub_clique: Clique,
                       slot_dims: Sequence[int]):
    """(region, shape) of subset A' in the merged-chain slot tensor.

    Axis i has ``slot_dims[i]`` slots: the measured part occupies slots
    ``0..slot_dims[i]−2`` when i ∈ A', the marginalized part the single last
    slot otherwise.  Distinct subsets occupy disjoint regions — the identity
    behind merged reconstruction (§5), shared by the plain path
    (slot_dims = n_i, :func:`embed_group`), the RP+ path (slot_dims = r_i+1,
    ``core/plus.py``) and the batched engine embedding
    (``engine/plus_engine.py``): one definition, three consumers.
    """
    sc = set(sub_clique)
    region = tuple(slice(0, r - 1) if i in sc else slice(r - 1, r)
                   for i, r in zip(clique, slot_dims))
    shape = tuple(r - 1 if i in sc else 1 for i, r in zip(clique, slot_dims))
    return region, shape


def embed_group(measurements: Mapping[Clique, Measurement],
                group: Sequence[Clique], sizes: Sequence[int],
                dtype=np.float64) -> np.ndarray:
    """Σ_{A'⊆A} e_{A'} for every clique A of one signature group, one row each.

    Returns the ``(len(group), Π sizes)`` input of the group's merged U-chain.
    A subset's slot region depends only on which positions of A it keeps, so
    each of the 2^k position patterns fills its region for all rows with one
    block copy: the rows' ω concatenated, cast to ``dtype`` in that single
    assignment.  The regions tile the tensor, so every cell is written.
    """
    positions, rows = tuple(range(len(sizes))), len(group)
    out = np.empty((rows, math.prod(sizes)), dtype=dtype)
    t = out.reshape((rows,) + tuple(sizes))
    cols = tuple(zip(*group))             # cols[j]: every row's j-th attribute
    for pos in subsets(positions):
        region, shape = subset_slot_region(positions, pos, sizes)
        keys = zip(*(cols[j] for j in pos)) if pos else [()] * rows
        t[(slice(None),) + region] = np.concatenate(
            [measurements[key].omega for key in keys],
            axis=None).reshape((rows,) + shape)
    return out


def embed_subset_answers(plan: Plan, measurements: Mapping[Clique, Measurement],
                         clique: Clique, dtype=np.float64) -> np.ndarray:
    """Sum of subset embeddings Σ_{A'⊆A} e_{A'} — input of the merged U-chain."""
    sizes = plan.domain.clique_sizes(clique)
    return embed_group(measurements, [clique], sizes, dtype).reshape(sizes)


def reconstruct_marginal(plan: Plan, measurements: Mapping[Clique, Measurement],
                         clique: Clique, xp=np) -> np.ndarray:
    """Unbiased noisy answer to the marginal on ``clique`` (Algorithm 2).

    xp: np for the float64 host path, jnp for the device path.
    """
    n_cells = plan.domain.n_cells(clique)
    q = None
    matvec = kron_matvec_np if xp is np else kron_matvec
    for sub in subsets(clique):
        omega = measurements[sub].omega
        if not clique:
            term = xp.asarray(omega, dtype=float).reshape(-1)
        else:
            factors, in_dims = _u_factors(plan.domain, clique, sub)
            term = matvec(factors, xp.asarray(omega).reshape(-1), in_dims)
        q = term if q is None else q + term
    assert q is not None and q.shape[0] == n_cells
    return q


def reconstruct_marginal_fast(plan: Plan, measurements: Mapping[Clique, Measurement],
                              clique: Clique, use_kernel: bool = False,
                              xp=np) -> np.ndarray:
    """Algorithm 2 as ONE Kronecker chain instead of 2^|A| subset matvecs.

    Embeds all subset answers into disjoint slots of one (n_i)_{i∈A} tensor
    (see :func:`u_chain_factors`) and applies the merged chain ⊗ T_i once —
    on the fused Pallas path when ``use_kernel``.
    """
    if not clique:
        return xp.asarray(measurements[()].omega, dtype=float).reshape(-1)
    sizes = plan.domain.clique_sizes(clique)
    t = embed_subset_answers(plan, measurements, clique)
    factors = u_chain_factors(plan.domain, clique)
    if use_kernel:
        from repro.kernels.kron_matvec.fused import fused_chain_matvec
        # Reconstruction carries no noise lanes: a tuned narrow compute dtype
        # (fp32 accumulation) may serve it (docs/DESIGN.md §14).
        return np.asarray(fused_chain_matvec(factors, t.reshape(-1), sizes,
                                             allow_narrow=True))
    matvec = kron_matvec_np if xp is np else kron_matvec
    return matvec(factors, t.reshape(-1), sizes)


def reconstruct_all(plan: Plan, measurements: Mapping[Clique, Measurement],
                    xp=np) -> Dict[Clique, np.ndarray]:
    return {c: reconstruct_marginal(plan, measurements, c, xp) for c in plan.workload.cliques}


def reconstruct_all_batched(plan: Plan, measurements: Mapping[Clique, Measurement],
                            cliques: Optional[Sequence[Clique]] = None,
                            use_kernel: Optional[bool] = None
                            ) -> Dict[Clique, np.ndarray]:
    """Batched Algorithm 2: same-signature marginals share one kernel chain.

    Marginals are grouped by attribute-size signature (they share the merged
    U-chain ⊗ T_i exactly), their embedded subset-answer tensors are stacked
    into the batch axis, and each group runs as a single fused chain
    (docs/DESIGN.md §5) — 2^|A| × #cliques matvecs collapse to one pallas_call
    per signature, and every group of the call runs in one compiled program.

    ``use_kernel=None`` resolves per backend: the fused Pallas chain on TPU,
    the batched jnp path elsewhere (interpret-mode Pallas is a correctness
    vehicle, not a CPU fast path — see benchmarks/kernels_bench.py).
    """
    from .mechanism import signature_groups
    from repro.kernels.kron_matvec._layout import resolve_use_kernel
    from repro.kernels.kron_matvec.fused import prepare_chain
    use_kernel = resolve_use_kernel(use_kernel)
    cliques = list(plan.workload.cliques if cliques is None else cliques)
    out: Dict[Clique, np.ndarray] = {}
    specs, args, groups = [], [], []
    for sizes, group in signature_groups(plan.domain, cliques).items():
        if not sizes:
            for c in group:
                out[c] = np.asarray(measurements[()].omega, dtype=float).reshape(-1)
            continue
        dims = "x".join(map(str, sizes))
        with TRACER.span("reconstruct.prepare").set(dims=dims):
            factors = u_chain_factors(plan.domain, group[0])
            launch = None
            if use_kernel:
                # Reconstruction carries no noise lanes: a tuned narrow
                # compute dtype (fp32 accumulation) may serve it
                # (docs/DESIGN.md §14).
                launch = prepare_chain(factors, sizes, len(group),
                                       allow_narrow=True)
                operands = launch.operands
            else:
                operands = tuple(jnp.asarray(f) for f in factors)
        with TRACER.span("reconstruct.embed").set(
                dims=dims, rows=len(group), copies=2 ** len(sizes)):
            x = embed_group(measurements, group, sizes,
                            np.float64 if launch is None else np.float32)
            if launch is not None and not launch.fused:
                # the per-axis chain takes it N-D
                x = x.reshape((len(group),) + tuple(sizes))
        specs.append((tuple(sizes), launch))
        args.append((x, operands))
        groups.append(group)
    if not specs:
        return out
    args = tuple(args)
    with TRACER.span("reconstruct.dispatch") as sp:
        ys = _reconstruct_program(tuple(specs))(args)
        if sp:
            sp.set(bytes=host_bytes(args))
    with TRACER.span("reconstruct.fetch") as sp:
        ys = [np.asarray(y) for y in ys]
        if sp:
            sp.set(bytes=sum(y.nbytes for y in ys))
    for (sizes, launch), group, y in zip(specs, groups, ys):
        if launch is not None:
            launch.record(len(group))
        y = y.reshape(len(group), -1)
        for i, c in enumerate(group):
            out[c] = y[i]
    return out


@lru_cache(maxsize=64)
def _reconstruct_program(specs):
    """One jitted program for every signature group of a reconstruction."""
    from .kron import kron_matvec_batched

    def run(args):
        return tuple(kron_matvec_batched(operands, x, sizes) if launch is None
                     else launch.apply(operands, x)
                     for (sizes, launch), (x, operands) in zip(specs, args))

    return jax.jit(run)


def marginal_variance(plan: Plan, clique: Clique) -> float:
    """Per-cell variance of the reconstructed marginal (Theorem 4) — all cells equal."""
    return plan.marginal_variance(clique)


def marginal_covariance_dense(plan: Plan, clique: Clique) -> np.ndarray:
    """Full covariance matrix of the reconstructed marginal on ``clique``.

    Cov = Σ_{A'⊆A} σ²_{A'} · ⊗_{i∈A} G_i   with
        G_i = Sub† (Sub Subᵀ) Sub†ᵀ   for i ∈ A'
        G_i = (1/n²) 11ᵀ              for i ∈ A \\ A'

    Materializes the n_cells × n_cells matrix — small cliques only.  The paper
    emphasises that per-cell variances and within-marginal covariances are
    available in closed form; this is that closed form, used for CI tests.
    """
    from .kron import kron_expand
    from .residual import sub_gram, sub_pinv

    dom = plan.domain
    n = dom.n_cells(clique)
    cov = np.zeros((n, n))
    for sub in subsets(clique):
        facs = []
        for i in clique:
            sz = dom.attributes[i].size
            if i in set(sub):
                sp = sub_pinv(sz)
                facs.append(sp @ sub_gram(sz) @ sp.T)
            else:
                facs.append(np.full((sz, sz), 1.0 / sz ** 2))
        cov += plan.sigmas[sub] * (kron_expand(facs) if facs else np.ones((1, 1)))
    return cov


def cross_marginal_covariance_dense(plan: Plan, a: Clique, b: Clique
                                    ) -> np.ndarray:
    """Full cross-covariance matrix of reconstructed marginals A and B.

    Only the measurements on shared subsets A' ⊆ A∩B correlate the two
    reconstructions:

        Cov(Q̂_A, Q̂_B) = Σ_{A'⊆A∩B} σ²_{A'} · U_{A←A'} H_{A'} H_{A'}ᵀ U_{B←A'}ᵀ

    with H_{A'} = ⊗_{i∈A'} Sub_{n_i}.  Materializes n_cells(A) × n_cells(B) —
    small cliques only; the fp64 oracle behind the IR's aligned-cell
    ``cross_covariance`` (docs/DESIGN.md §9).
    """
    from .kron import kron_expand
    from .residual import sub_matrix

    dom = plan.domain
    inter = tuple(sorted(set(a) & set(b)))
    cov = np.zeros((dom.n_cells(a), dom.n_cells(b)))
    for sub in subsets(inter):
        ua = kron_expand(_u_factors(dom, a, sub)[0]) if a else np.ones((1, 1))
        ub = kron_expand(_u_factors(dom, b, sub)[0]) if b else np.ones((1, 1))
        h = kron_expand([sub_matrix(dom.attributes[i].size) for i in sub]) \
            if sub else np.ones((1, 1))
        cov += plan.sigmas[sub] * ua @ h @ h.T @ ub.T
    return cov
