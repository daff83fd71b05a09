r"""DiscreteEngine: the secure release path at fused-engine tier (Alg 3).

``measure_discrete`` (core/discrete.py) is the host-exact reference: per
clique, ``kron_matvec_np`` for H = ⊗(n_i·I − 11ᵀ) and Y† = ⊗ Sub†/n_i around
a serial noise draw.  This engine is the serving-grade rebuild
(docs/DESIGN.md §10): the same mechanism, but

* **signature-batched device transforms** — cliques with equal attribute-size
  signatures stack into the batch axis of ONE fused Kron chain per group for
  both H (forward) and Y† (reconstruction), exactly like
  :class:`~repro.engine.engine.MarginalEngine` batches Algorithm 1.  No
  per-clique ``kron_matvec_np`` remains on the hot path (test-enforced);
* **host-exact noise only** — the discrete Gaussian draw runs through the
  batched integer-lane sampler (:mod:`repro.core.dgauss`), pooled across the
  cliques of a group that share γ².  Exactness of the *noise* is what the
  privacy proof needs; it never leaves the host;
* **an explicit exactness boundary for H** — Ξx = Hv must be released as
  exact integers.  The engine bounds ‖Hv‖∞ from the actual tables
  (ℓ1-growth: ‖v‖₁·Π 2n_i, times max n_i for intermediates) and routes the
  group to the device chain + ``rint`` only while every intermediate is
  exactly representable in the chain dtype's mantissa; beyond that the group
  falls back to an *exact integer* batched tensordot (int64, then Python
  big-int lanes) — still one transform per group, never per clique.
  Y† is post-processing (Thm 6): device floats are always acceptable there,
  with a float64 host fallback only to keep huge-γ² lanes finite in f32.

Usage::

    engine = plan.engine(secure=True)        # or DiscreteEngine(plan)
    meas   = engine.measure(marginals, key)  # key: jax key / np Generator /
    tables = engine.reconstruct(meas)        #      random.Random
    tables, meas = engine.release(marginals, key)
"""
from __future__ import annotations

import random
from collections import defaultdict
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import dgauss
from repro.core.discrete import (DiscreteMeasurement, clique_gamma2,
                                 discrete_pcost_of_plan, h_factors,
                                 ypinv_factors)
from repro.core.domain import Clique
from repro.core.kron import kron_matvec_batched, kron_matvec_np_batched
from repro.core.mechanism import noise_dtype, signature_groups
from repro.core.plantable import BasePlan
from repro.core.reconstruct import reconstruct_all_batched, u_chain_factors
from repro.engine.engine import ChainRegistry, EngineStats, ReleaseServing
from repro.obs import TRACER
from repro.kernels.kron_matvec._layout import resolve_use_kernel
from repro.kernels.kron_matvec.fused import fused_chain_matvec

# f32 chains hold integers exactly below 2^24, f64 below 2^53.
_MANTISSA_BITS = {"float32": 24, "float64": 53}


# Exact host fallback: one batched tensordot chain per group, any dtype
# (int64 / object big-int / float64) — batched, never per clique.
_np_chain_batched = kron_matvec_np_batched


def as_np_rng(key) -> np.random.Generator:
    """Normalize a randomness source (jax key / Generator / Random).

    jax keys seed a ``SeedSequence`` from their raw key data, so the secure
    path keeps the engines' key-passing convention (``measure(margs, key)``)
    while the draws stay host-side and exact.
    """
    if isinstance(key, (np.random.Generator, random.Random)):
        return dgauss.as_np_rng(key)
    try:
        data = np.asarray(jax.random.key_data(key))
    except (TypeError, AttributeError):
        data = np.asarray(key)
    data = np.atleast_1d(data).reshape(-1).astype(np.uint32)
    return np.random.default_rng(np.random.SeedSequence(data.tolist()))


class DiscreteEngine(ReleaseServing, ChainRegistry):
    """Compile a plan's secure-release chains once; serve Alg 3 traffic.

    Parameters
    ----------
    plan:        selection-phase output over a *plain* (identity-basis) IR —
                 the integer-query rotation does not exist for RP+ bases.
    use_kernel:  route chains through the fused Pallas kernel or the batched
                 jnp path; ``None`` resolves per backend like the other
                 engines (Pallas on TPU, batched jnp elsewhere).
    precompile:  trace/compile every chain at construction.
    dtype:       device-transform dtype; ``None`` resolves to
                 :func:`repro.core.mechanism.noise_dtype`.  Only the H
                 exactness bound and Y† precision depend on it — the noise
                 itself is integer-exact regardless.
    digits:      σ̄ rationalization digits (Alg 3 line 1 / §5.2).
    """

    def __init__(self, plan: BasePlan, use_kernel: Optional[bool] = None,
                 precompile: bool = True, dtype=None, digits: int = 4):
        if not getattr(plan.table, "plain", True):
            raise ValueError("DiscreteEngine requires a plain (identity-basis)"
                             " plan; RP+ plans have no integer-query rotation")
        self.plan = plan
        self.digits = digits
        self.use_kernel = resolve_use_kernel(use_kernel)
        self.dtype = noise_dtype() if dtype is None else dtype
        self.stats = EngineStats()
        # Exact per-clique σ̄/γ² (Alg 3 lines 1-2), computed once.
        self.sigma_bars: Dict[Clique, object] = {}
        self.gamma2s: Dict[Clique, object] = {}
        for c in plan.cliques:
            sb, g2, _ = clique_gamma2(plan, c, digits)
            self.sigma_bars[c] = sb
            self.gamma2s[c] = g2
        self._groups = signature_groups(plan.domain, plan.cliques)
        self._reconstruct_groups = signature_groups(plan.domain,
                                                    plan.workload.cliques)
        self.stats.measure_signatures = len(self._groups)
        self.stats.reconstruct_signatures = len(self._reconstruct_groups)
        self._chain_plans: Dict[tuple, object] = {}
        for dims, cliques in self._groups.items():
            if dims:
                self._register_chain(h_factors(dims), dims,
                                     len(cliques))
                self._register_chain(ypinv_factors(dims), dims, len(cliques))
        for dims, cliques in self._reconstruct_groups.items():
            if dims:
                self._register_chain(u_chain_factors(plan.domain, cliques[0]),
                                     dims, len(cliques))
        if precompile and self.use_kernel:
            self._warmup()

    def _warmup(self) -> None:
        for key, (cp, factors, batch, _epi) in self._chain_plans.items():
            x = jnp.zeros((batch, cp.n_in), jnp.float32)
            fused_chain_matvec(factors, x, key[0]).block_until_ready()
            self.stats.bump("compile_warmups")

    # ------------------------------------------------------------ transforms
    def _device_chain(self, factors: List[np.ndarray], x: np.ndarray,
                      dims: Tuple[int, ...]) -> np.ndarray:
        if self.use_kernel:
            y = fused_chain_matvec(factors, jnp.asarray(x, jnp.float32), dims)
        else:
            y = kron_matvec_batched(
                [jnp.asarray(f, self.dtype) for f in factors],
                jnp.asarray(x, self.dtype), dims)
        return np.asarray(y, np.float64)

    def _chain_dtype_name(self) -> str:
        return "float32" if self.use_kernel else jnp.dtype(self.dtype).name

    def _h_transform(self, vs: np.ndarray, dims: Tuple[int, ...]) -> np.ndarray:
        """Exact Ξx = Hv for a stacked group of marginal tables (counts).

        Device chain + ``rint`` while every intermediate provably stays
        inside the chain dtype's exact-integer range; exact host int64 /
        big-int batched tensordot beyond (stats-counted).  Every tier returns
        *exact integers* — as int64 when they fit, object (Python big-int)
        lanes beyond — so the noise addition downstream is exact too.
        """
        # ℓ1 growth bound: per axis ‖(nI-11ᵀ)u‖₁ ≤ 2n‖u‖₁, and intermediates
        # inside a dot are ≤ max(n)·running bound.
        l1 = float(np.abs(vs).sum(axis=1).max(initial=0.0))
        growth = 1.0
        for n in dims:
            growth *= 2 * n
        bound = l1 * growth * max(dims)
        mant = _MANTISSA_BITS[self._chain_dtype_name()]
        if bound < float(1 << mant):
            self.stats.bump("device_h_groups")
            hv = np.rint(self._device_chain(
                h_factors(dims), vs, dims))
            return hv.astype(np.int64)
        self.stats.bump("exact_h_groups")
        facs = h_factors(dims, np.int64)
        if bound < float(1 << 62):
            return _np_chain_batched(facs, np.rint(vs).astype(np.int64), dims)
        obj = np.array([[int(v) for v in row] for row in np.rint(vs)],
                       dtype=object)
        return _np_chain_batched([f.astype(object) for f in facs], obj, dims)

    def _y_transform(self, noisy: np.ndarray, dims: Tuple[int, ...]
                     ) -> np.ndarray:
        """Y† = ⊗ Sub†/n on the noisy integers — post-processing (Thm 6),
        device floats by design; float64 host fallback only when huge-γ²
        lanes would overflow a float32 chain."""
        if self._chain_dtype_name() == "float32" and \
                float(np.abs(noisy).max(initial=0.0)) >= 3e38:
            self.stats.bump("host_y_groups")
            return _np_chain_batched(ypinv_factors(dims),
                                     np.asarray(noisy, np.float64), dims)
        return self._device_chain(ypinv_factors(dims), noisy, dims)

    # ----------------------------------------------------------------- noise
    def _draw_group(self, cliques: List[Clique], n_prod: int,
                    rng: np.random.Generator) -> Dict[Clique, np.ndarray]:
        """Pooled integer-lane draws: cliques sharing γ² share one batched
        ``dgauss.sample`` call (γ² differs only when σ̄ does)."""
        by_gamma2 = defaultdict(list)
        for c in cliques:
            by_gamma2[self.gamma2s[c]].append(c)
        out: Dict[Clique, np.ndarray] = {}
        for g2, cs in by_gamma2.items():
            z = dgauss.sample(g2, n_prod * len(cs), rng)
            for i, c in enumerate(cs):
                out[c] = z[i * n_prod:(i + 1) * n_prod]
        return out

    # ----------------------------------------------------------------- serve
    def measure(self, marginals: Mapping[Clique, np.ndarray], key,
                _noise_override=None) -> Dict[Clique, DiscreteMeasurement]:
        """Algorithm 3 over the whole closure: one fused H chain and one
        fused Y† chain per signature group, host-exact noise in between.

        ``key`` may be a jax PRNG key, an ``np.random.Generator`` or a
        ``random.Random`` (see :func:`as_np_rng`); draws are
        seed-deterministic per key.
        """
        self.stats.bump("measure_calls")
        with TRACER.span("engine.measure").set(
                engine="discrete", cliques=len(self.plan.cliques),
                use_kernel=self.use_kernel):
            return self._measure_impl(marginals, key, _noise_override)

    def _measure_impl(self, marginals, key, _noise_override=None):
        rng = as_np_rng(key)
        out: Dict[Clique, DiscreteMeasurement] = {}
        for dims, cliques in self._groups.items():
            if not dims:
                for c in cliques:
                    v = np.asarray(marginals[c], np.float64).reshape(-1)
                    z = (_noise_override(self.gamma2s[c], 1, rng)
                         if _noise_override is not None
                         else dgauss.sample(self.gamma2s[c], 1, rng))
                    sb = self.sigma_bars[c]
                    out[c] = DiscreteMeasurement(
                        c, v + np.asarray(z, np.float64), float(sb ** 2),
                        sb, self.gamma2s[c])
                continue
            m = int(np.prod(dims))
            g = len(cliques)
            vs = np.empty((g, m), np.float64)
            for i, c in enumerate(cliques):
                v = np.asarray(marginals[c], np.float64).reshape(-1)
                if v.shape[0] != m:
                    raise ValueError(
                        f"marginal for {c} has {v.shape[0]} cells, want {m}")
                vs[i] = v
            hv = self._h_transform(vs, dims)                       # = Ξx, exact
            if _noise_override is not None:
                zs = {c: _noise_override(self.gamma2s[c], m, rng)
                      for c in cliques}
            else:
                zs = self._draw_group(cliques, m, rng)
            # M'(x) = Ξx + z summed in exact integer arithmetic; the single
            # float64 conversion of the sum is post-processing (DESIGN §10).
            noisy = np.empty((g, m), np.float64)
            for i, c in enumerate(cliques):
                z = np.asarray(zs[c])
                if hv.dtype == object or z.dtype == object:
                    s = hv[i].astype(object) + z.astype(object)
                else:
                    s = hv[i] + z                  # int64, |Ξx| + |z| < 2^63
                noisy[i] = s.astype(np.float64)
            om = self._y_transform(noisy, dims)
            for i, c in enumerate(cliques):
                sb = self.sigma_bars[c]
                out[c] = DiscreteMeasurement(c, om[i], float(sb ** 2),
                                             sb, self.gamma2s[c])
        return out

    def reconstruct(self, measurements: Mapping[Clique, DiscreteMeasurement],
                    cliques: Optional[Sequence[Clique]] = None
                    ) -> Dict[Clique, np.ndarray]:
        """Algorithm 2 on the discrete measurements (drop-in ω): batched
        merged U-chains, shared with the continuous engine."""
        self.stats.bump("reconstruct_calls")
        with TRACER.span("engine.reconstruct").set(
                engine="discrete", use_kernel=self.use_kernel):
            return reconstruct_all_batched(self.plan, measurements, cliques,
                                           use_kernel=self.use_kernel)

    # release()/synthesize() come from ReleaseServing; the secure path pins
    # the consistency fit to the *measured integer total*, so postprocessed
    # families preserve it integer-exactly (DESIGN.md §11).
    def _postprocess_total(self, measurements) -> float:
        from repro.release import measured_integer_total
        return measured_integer_total(measurements)

    # ------------------------------------------------------------ accounting
    def rho(self) -> float:
        """Total ρ-zCDP actually spent at the rationalized σ̄ (Thm 6)."""
        return discrete_pcost_of_plan(self.plan, self.digits) / 2.0

    def pcost(self) -> float:
        """pcost (= 2ρ) for :class:`~repro.core.accountant.PrivacyBudget`."""
        return discrete_pcost_of_plan(self.plan, self.digits)
