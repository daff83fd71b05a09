"""Measurement phase: run the base mechanisms  M_A(x; σ²_A) = R_A x + N(0, σ²_A Σ_A).

Implements Algorithm 1 of the paper: the residual answer is computed from the
*marginal table* on A (never from the full data vector):

    v  = Q_A x                      (marginal on A, shape Π_{i∈A} n_i)
    H  = ⊗_{i∈A} Sub_{n_i}          (implicit Kronecker factors)
    ω  = H v + σ_A · H z,   z ~ N(0, I)

so the noise H z has exactly the covariance σ²_A Σ_A = σ²_A H Hᵀ.

The device path (`measure`) uses jnp + the Pallas kron kernels when enabled;
`measure_np` is the float64 host oracle used by tests.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Mapping, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from .domain import Clique, Domain
from .kron import kron_matvec, kron_matvec_np
from .residual import p_coeff, sub_matrix
from .select import Plan


@dataclass
class Measurement:
    clique: Clique
    omega: np.ndarray          # noisy residual answer, shape Π_{i∈A}(n_i - 1)
    sigma2: float


def pcost_of_plan(plan: Plan) -> float:
    """Total privacy cost Σ_A p_A / σ²_A (Thm 3)."""
    return sum(p_coeff(plan.domain, c) / s for c, s in plan.sigmas.items())


def _clique_dims(domain: Domain, clique: Clique) -> List[int]:
    return [domain.attributes[i].size for i in clique]


def residual_answer(domain: Domain, clique: Clique, marginal: jnp.ndarray,
                    use_kernel: bool = False) -> jnp.ndarray:
    """H v — the exact residual query answer from the marginal table on ``clique``."""
    dims = _clique_dims(domain, clique)
    if not clique:
        return jnp.asarray(marginal).reshape(-1)
    factors = [sub_matrix(n) for n in dims]
    if use_kernel:
        from repro.kernels.kron_matvec.ops import kron_matvec_kernel
        return kron_matvec_kernel(factors, jnp.asarray(marginal), dims)
    return kron_matvec(factors, jnp.asarray(marginal), dims)


def signature_groups(domain: Domain, cliques: Sequence[Clique],
                     axis_key=None) -> Dict[tuple, List[Clique]]:
    """Group cliques by per-axis signature (docs/DESIGN.md §4, §8).

    ``axis_key(i)`` maps an attribute index to a hashable per-axis token; the
    default is the attribute size, under which cliques with equal signatures
    share the exact same Kronecker factor chain ``⊗_i Sub_{n_i}`` (the
    plain-marginal chain is fully determined by the size).  ResidualPlanner+
    passes a token that also carries the per-attribute ``(Sub_i, Γ_i)`` factor
    shapes and values (``plus_axis_token`` in ``core/plus.py``), since
    Γ_i ≠ Sub_i for non-identity bases and equal sizes no longer imply equal
    chains.  Cliques in one group stack into the batch axis of a single kernel
    chain.  Insertion order preserves the input clique order within each group.
    """
    from collections import defaultdict
    if axis_key is None:
        axis_key = lambda i: domain.attributes[i].size  # noqa: E731
    groups: Dict[tuple, List[Clique]] = defaultdict(list)
    for clique in cliques:
        groups[tuple(axis_key(i) for i in clique)].append(clique)
    return dict(groups)


def noise_dtype():
    """Default dtype for Gaussian noise draws: float64 iff jax x64 is enabled.

    Every measurement path (core, engine, sharded) threads its noise dtype
    from here unless explicitly overridden, so device and host draws agree.
    """
    return jnp.float64 if jax.config.read("jax_enable_x64") else jnp.float32


_noise_dtype = noise_dtype   # backward-compat alias


def measure(plan: Plan, marginals: Mapping[Clique, jnp.ndarray],
            key: jax.Array, use_kernel: Optional[bool] = None,
            batched: bool = True, dtype=None) -> Dict[Clique, Measurement]:
    """Run every base mechanism in the plan (Algorithm 1, continuous Gaussian).

    ``marginals[A]`` must hold the exact marginal table for every A in the
    plan's closure (flattened or tensor shaped).  Base mechanisms are
    independent; each consumes its own fold of ``key`` — the fold order is
    fixed by ``plan.cliques`` so batched and loop execution draw identical
    noise.

    ``batched=True`` (default) groups cliques by attribute-size signature and
    stacks all ``[v; z]`` pairs of a group into the batch axis of ONE kernel
    chain per group (fused Pallas chain when ``use_kernel``, batched jnp
    otherwise) instead of launching one chain per clique.  ``batched=False``
    keeps the historical per-clique loop (oracle / benchmark baseline).

    ``dtype`` governs the noise draws; ``None`` resolves to
    :func:`noise_dtype` (float64 under jax x64).  ``use_kernel=None``
    resolves from the backend (the Pallas chains on a TPU).
    """
    if batched:
        from repro.engine.multi import measure_batch
        return measure_batch([(plan, marginals, key)], use_kernel, dtype)[0]
    from repro.kernels.kron_matvec._layout import resolve_use_kernel
    dtype = _noise_dtype() if dtype is None else dtype
    keys = np.asarray(jax.random.split(key, len(plan.cliques)))
    return _measure_loop(plan, marginals, dict(zip(plan.cliques, keys)),
                         resolve_use_kernel(use_kernel), dtype)


def _measure_loop(plan: Plan, marginals: Mapping[Clique, jnp.ndarray],
                  keymap: Mapping[Clique, jax.Array],
                  use_kernel: bool, dtype=None) -> Dict[Clique, Measurement]:
    """Historical per-clique device loop — one chain per clique (bench baseline)."""
    out: Dict[Clique, Measurement] = {}
    dtype = _noise_dtype() if dtype is None else dtype
    for clique in plan.cliques:
        dims = _clique_dims(plan.domain, clique)
        v = jnp.asarray(marginals[clique]).reshape(-1)
        m = int(np.prod(dims)) if clique else 1
        if v.shape[0] != m:
            raise ValueError(f"marginal for {clique} has {v.shape[0]} cells, want {m}")
        sigma = math.sqrt(plan.sigmas[clique])
        z = jax.random.normal(keymap[clique], (m,), dtype=dtype)
        hv = residual_answer(plan.domain, clique, v, use_kernel)
        hz = residual_answer(plan.domain, clique, z, use_kernel)
        out[clique] = Measurement(clique, np.asarray(hv + sigma * hz), plan.sigmas[clique])
    return out


def measure_np(plan: Plan, marginals: Mapping[Clique, np.ndarray],
               rng: np.random.Generator) -> Dict[Clique, Measurement]:
    """Host float64 oracle of `measure` (tests, tiny problems)."""
    out: Dict[Clique, Measurement] = {}
    for clique in plan.cliques:
        dims = _clique_dims(plan.domain, clique)
        v = np.asarray(marginals[clique], dtype=np.float64).reshape(-1)
        if not clique:
            out[clique] = Measurement(clique, v + math.sqrt(plan.sigmas[clique])
                                      * rng.standard_normal(1), plan.sigmas[clique])
            continue
        factors = [sub_matrix(n) for n in dims]
        z = rng.standard_normal(int(np.prod(dims)))
        hv = kron_matvec_np(factors, v, dims)
        hz = kron_matvec_np(factors, z, dims)
        out[clique] = Measurement(clique, hv + math.sqrt(plan.sigmas[clique]) * hz,
                                  plan.sigmas[clique])
    return out


def measure_np_batched(plan: Plan, marginals: Mapping[Clique, np.ndarray],
                       rng: np.random.Generator, chunk: int = 64
                       ) -> Dict[Clique, Measurement]:
    """Batched measurement (§Perf iteration M1/M2): base mechanisms with the
    same attribute-size signature share stacked kron-matvecs, processed in
    cache-resident chunks.

    Measured on this container (Synth-10^d, all ≤3-way): 5.1× (d=20) and
    4.1× (d=50) over the per-clique loop at chunk=64; a single monolithic
    batch is only ~1.2× (refuted hypothesis M1 — the 300 MB stack thrashes
    cache; see EXPERIMENTS.md §Perf).  The batch axis is the same "left"
    dimension the Pallas kernel tiles on TPU.
    """
    out: Dict[Clique, Measurement] = {}
    for dims, cliques in signature_groups(plan.domain, plan.cliques).items():
        m = int(np.prod(dims)) if dims else 1
        for s0 in range(0, len(cliques), chunk):
            cs = cliques[s0:s0 + chunk]
            g = len(cs)
            v = np.stack([np.asarray(marginals[c], dtype=np.float64).reshape(-1)
                          for c in cs])
            z = rng.standard_normal((g, m))
            if dims:
                x = np.concatenate([v, z], axis=0).reshape((2 * g,) + dims)
                for axis, n in enumerate(dims):
                    s = sub_matrix(n)
                    x = np.moveaxis(
                        np.tensordot(s, np.moveaxis(x, axis + 1, 0),
                                     axes=([1], [0])), 0, axis + 1)
                x = x.reshape(2 * g, -1)
                hv, hz = x[:g], x[g:]
            else:
                hv, hz = v, z
            sig = np.array([math.sqrt(plan.sigmas[c]) for c in cs])[:, None]
            om = hv + sig * hz
            for i, c in enumerate(cs):
                out[c] = Measurement(c, om[i], plan.sigmas[c])
    return out


def exact_marginals_from_x(domain: Domain, cliques: Sequence[Clique],
                           x: np.ndarray) -> Dict[Clique, np.ndarray]:
    """Marginal tables Q_A x from a full contingency vector (small domains/tests)."""
    x = np.asarray(x, dtype=np.float64).reshape(domain.sizes)
    out = {}
    for c in cliques:
        keep = set(c)
        axes = tuple(i for i in range(domain.n_attrs) if i not in keep)
        out[c] = x.sum(axis=axes).reshape(-1)
    return out
