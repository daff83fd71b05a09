"""PlusEngine: compile-once, signature-batched serving of ResidualPlanner+.

The pure-marginal path got the fused Kron-chain kernel, signature batching
and compile-once serving in PR 1 (engine/engine.py); this module closes the
gap for the paper's "+" workloads (§7, Algs 5/6): marginals mixed with
range / prefix-sum / custom per-attribute bases.

Three generalizations over :class:`repro.engine.engine.MarginalEngine`
(docs/DESIGN.md §8):

* **Generalized signatures** — cliques batch by per-axis ``(Sub_i, Γ_i, W_i)``
  factor shape + value tokens (``plus_signature_groups``), not attribute
  sizes: Γ_i ≠ Sub_i for non-identity bases, so equal sizes no longer imply
  equal chains.
* **Staged [v; z] measurement** — ω = (⊗Sub_i) v + σ(⊗Γ_i) z runs as at most
  two chains per group: stage A applies the general-axis ``Sub_i`` to the
  v rows (Γ_i = I there, so the noise stream skips those axes), stage B rides
  the stacked ``[v'; z]`` pairs of the whole group down the identity-axis
  chain.  All-identity groups degenerate to PR 1's single chain; all-general
  groups need no stage B chain at all.
* **Merged reconstruction with an implicit-W epilogue** — Algorithm 6's
  2^|A| subset matvecs collapse into ONE chain per workload clique via the
  generalized T_i = [Sub_i† | (1/n_i)·1] embedding, with W_i folded into the
  chain factor (identity/total/custom) or applied implicitly: prefix as a
  cumsum epilogue, range as cumsum + prefix-difference gathers — the
  O(n²)-row ``w_range`` matrix never enters a dense matvec on the hot path.

Every per-group transform is compiled exactly once: on the batched-jnp path
(CPU/GPU default) the whole group pipeline — chains, epilogue, range
expansion, [v; z] noise combine — is one ``jax.jit`` cache entry keyed on the
group signature; on the Pallas path the fused chains go through the
``fused_chain_matvec`` kernel cache (with in-kernel epilogues) and only the
shape-changing range expansion is jitted separately.  Noise is drawn as one
vectorized per-group fold gather, never one dispatch per clique.

Usage::

    engine = PlusEngine(plan)                    # plan: core.plus.select_plus
    meas   = engine.measure(marginals, key)      # Alg 5, batched on device
    tables = engine.reconstruct(meas)            # Alg 6, batched on device
    tables, meas = engine.release(marginals, key)
"""
from __future__ import annotations

import math
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.domain import Clique
from repro.core.kron import kron_matvec_batched, kron_out_dims
from repro.core.mechanism import Measurement, noise_dtype
from repro.core.plus import (PlusPlan, measure_chain_split,
                             plus_signature_groups, t_chain_factors_plus)
from repro.core.reconstruct import subset_slot_region
from repro.engine.engine import ChainRegistry, EngineStats, ReleaseServing
from repro.kernels.kron_matvec._layout import resolve_use_kernel
from repro.kernels.kron_matvec.fused import apply_epilogue, fused_chain_matvec
from repro.kernels.kron_matvec.stats import CHAIN_STATS
from repro.obs import TRACER


def expand_range_axis(t: jnp.ndarray, axis: int, n: int) -> jnp.ndarray:
    """Implicit ``w_range`` from per-axis prefix sums: rows p[b] − p[a−1].

    ``t`` carries cumulative sums along ``axis`` (the cumsum epilogue output,
    size n); n static slice-subtracts expand them to all n(n+1)/2 contiguous
    ranges in ``w_range`` row order (a-major) without ever touching the dense
    O(n²)-row matrix.  Contiguous slices beat a 2×n(n+1)/2 gather on every
    backend.
    """
    p = jnp.moveaxis(t, axis, -1)
    parts = [p]                                      # a = 0: p[b]
    for a in range(1, n):
        parts.append(p[..., a:] - p[..., a - 1:a])   # p[b] − p[a−1], b ≥ a
    return jnp.moveaxis(jnp.concatenate(parts, axis=-1), -1, axis)


class PlusEngine(ReleaseServing, ChainRegistry):
    """Compile a PlusPlan's kernel chains once; serve Alg 5/6 traffic.

    Parameters
    ----------
    plan:        ``core.plus.select_plus`` output — a
                 :class:`~repro.core.plantable.BasePlan` carrying the RP+
                 PlanTable IR plus the per-attribute generalized bases
                 (``plan.schema``); σ² access goes through the unified
                 protocol (``plan.sigma2``).
    use_kernel:  route chains through the fused Pallas kernel or the jitted
                 batched jnp path.  The default ``None`` resolves per
                 backend — Pallas on TPU, batched jnp elsewhere.
    precompile:  trace/compile every chain at construction so serving calls
                 are cache hits (set False for tiny one-shot jobs).
    dtype:       noise-draw dtype; ``None`` resolves to
                 :func:`repro.core.mechanism.noise_dtype`.
    """

    def __init__(self, plan: PlusPlan, use_kernel: Optional[bool] = None,
                 precompile: bool = True, dtype=None):
        self.plan = plan
        self.schema = plan.schema
        self.use_kernel = resolve_use_kernel(use_kernel)
        self.dtype = noise_dtype() if dtype is None else dtype
        self.stats = EngineStats()
        self._pos = {c: i for i, c in enumerate(plan.cliques)}
        self._measure_groups = plus_signature_groups(self.schema, plan.cliques)
        self._reconstruct_groups = plus_signature_groups(
            self.schema, plan.workload.cliques)
        self.stats.measure_signatures = len(self._measure_groups)
        self.stats.reconstruct_signatures = len(self._reconstruct_groups)
        self._measure_specs = {
            tok: self._build_measure_spec(tok, cliques)
            for tok, cliques in self._measure_groups.items() if tok}
        # reconstruction state is built on first use (or at precompile):
        # measure-only consumers (e.g. sharded_measure) never pay for it.
        self._reconstruct_specs: Optional[Dict[tuple, dict]] = None
        self._chain_plans: Dict[tuple, object] = {}
        for tok, cliques in self._measure_groups.items():
            if not tok:
                continue
            spec = self._measure_specs[tok]
            dims, zdims, stage_a, stage_b = spec["split"]
            if any(f is not None for f in stage_a):
                self._register_chain(stage_a, dims, len(cliques),
                                     role="measure")
            if any(f is not None for f in stage_b):
                self._register_chain(stage_b, zdims, 2 * len(cliques),
                                     role="measure")
        if precompile:
            self._warmup()

    def _ensure_reconstruct_state(self) -> Dict[tuple, dict]:
        if self._reconstruct_specs is None:
            self._reconstruct_specs = {
                tok: self._build_reconstruct_spec(cliques[0])
                for tok, cliques in self._reconstruct_groups.items() if tok}
            for tok, cliques in self._reconstruct_groups.items():
                if tok:
                    spec = self._reconstruct_specs[tok]
                    self._register_chain(spec["factors"], spec["in_dims"],
                                         len(cliques), spec["epilogue"],
                                         role="reconstruct")
        return self._reconstruct_specs

    # ------------------------------------------------------------ group prep
    def _build_measure_spec(self, tok: tuple, cliques: List[Clique]) -> dict:
        dims, zdims, stage_a, stage_b = measure_chain_split(self.schema,
                                                            cliques[0])
        g = len(cliques)
        m = int(np.prod(dims)) if dims else 1
        mz = int(np.prod(zdims)) if zdims else 1
        sig = np.sqrt([self.plan.sigma2(c) for c in cliques])[:, None]
        has_a = any(f is not None for f in stage_a)
        has_b = any(f is not None for f in stage_b)
        a_facs = [None if f is None else jnp.asarray(f, jnp.float32)
                  for f in stage_a]
        b_facs = [None if f is None else jnp.asarray(f, jnp.float32)
                  for f in stage_b]
        sig_j = jnp.asarray(sig, jnp.float32)

        def combine(v_stack, z):
            """Staged Alg 5 for the whole group, one trace (jnp path)."""
            if has_a:
                v_stack = kron_matvec_batched(a_facs, v_stack, dims)
            x = jnp.concatenate([v_stack.astype(z.dtype), z], axis=0)
            if has_b:
                x = kron_matvec_batched(b_facs, x, zdims)
            return x[:g] + sig_j * x[g:]

        dtype = self.dtype

        def draw(ks):
            return jax.vmap(lambda k: jax.random.normal(k, (mz,), dtype))(ks)

        return dict(split=(dims, zdims, stage_a, stage_b), g=g, m=m, mz=mz,
                    sig=sig, has_a=has_a, has_b=has_b,
                    key_idx=np.asarray([self._pos[c] for c in cliques]),
                    combine=jax.jit(combine), draw=jax.jit(draw))

    def _build_reconstruct_spec(self, clique: Clique) -> dict:
        """Merged-chain layout for one reconstruction signature group.

        Per axis: the chain factor (T_i, or W_i·T_i when W_i is folded in),
        the in-chain epilogue op, and the post-chain range expansion indices
        (None unless kind == 'range').
        """
        factors: List[np.ndarray] = []
        in_dims: List[int] = []
        epilogue: List[Optional[str]] = []
        posts: List[Optional[int]] = []   # range axes: n (expansion size)
        for i, t_i in zip(clique, t_chain_factors_plus(self.schema, clique)):
            b = self.schema.bases[i]
            in_dims.append(t_i.shape[1])
            if b.kind in ("prefix", "range"):
                factors.append(t_i)
                epilogue.append("cumsum")
                posts.append(b.n if b.kind == "range" else None)
            else:   # identity / total / custom: fold W into the chain factor
                factors.append(b.W @ t_i)
                epilogue.append(None)
                posts.append(None)
        chain_out = kron_out_dims(factors, in_dims)
        facs_j = [jnp.asarray(f, jnp.float32) for f in factors]
        epilogue = tuple(epilogue)

        def expand(t):
            for axis, post in enumerate(posts):
                if post is not None:
                    t = expand_range_axis(t, axis + 1, post)
            return t.reshape(t.shape[0], -1)

        def full(x):
            """Chain + epilogue + expansion, one trace (jnp path)."""
            y = kron_matvec_batched(facs_j, x, in_dims)
            y = apply_epilogue(y, chain_out, epilogue)
            return expand(y.reshape((x.shape[0],) + tuple(chain_out)))

        return dict(factors=factors, in_dims=in_dims, epilogue=epilogue,
                    chain_out=chain_out, posts=posts,
                    expand=jax.jit(expand), full=jax.jit(full))

    def _warmup(self) -> None:
        """Trace/compile every per-group transform on zeros, so serving calls
        are jit/pallas cache hits at the exact shapes traffic will use."""
        self._ensure_reconstruct_state()
        if self.use_kernel:
            for key, (cp, factors, batch, epi) in self._chain_plans.items():
                dims = key[0]
                x = jnp.zeros((batch, cp.n_in), jnp.float32)
                fused_chain_matvec(
                    factors, x, dims, epilogue=epi,
                    allow_narrow=self._chain_allow_narrow(key)
                ).block_until_ready()
                self.stats.bump("compile_warmups")
        for tok in self._measure_groups:
            if not tok:
                continue
            s = self._measure_specs[tok]
            s["draw"](jnp.zeros((s["g"], 2), jnp.uint32))
            if not self.use_kernel:
                s["combine"](jnp.zeros((s["g"], s["m"]), jnp.float32),
                             jnp.zeros((s["g"], s["mz"]), self.dtype))
                self.stats.bump("compile_warmups")
        for tok, cliques in self._reconstruct_groups.items():
            if not tok:
                continue
            s = self._reconstruct_specs[tok]
            g = len(cliques)
            if self.use_kernel:
                s["expand"](jnp.zeros((g,) + tuple(s["chain_out"]),
                                      jnp.float32))
            else:
                s["full"](jnp.zeros((g, int(np.prod(s["in_dims"]))),
                                    jnp.float32))
                self.stats.bump("compile_warmups")

    # ---------------------------------------------------------------- noise
    def _fold_keys(self, key: jax.Array) -> jax.Array:
        """One key fold per base mechanism, in ``plan.cliques`` order."""
        return jax.random.split(key, len(self.plan.cliques))

    def _draw_empty(self, all_keys: jax.Array, clique: Clique) -> jnp.ndarray:
        return jax.random.normal(all_keys[self._pos[clique]], (1,), self.dtype)

    def _draw_group(self, all_keys: jax.Array, spec: dict) -> jnp.ndarray:
        return spec["draw"](all_keys[spec["key_idx"]])

    def noise_draws(self, key: jax.Array) -> Dict[Clique, np.ndarray]:
        """The per-clique Gaussian draws ``measure(·, key)`` consumes.

        Shares the exact fold/draw helpers with :meth:`measure`, so the
        values are identical whether serving runs the kernel or the jnp
        path.  Exposed so tests can replay the exact noise into the numpy
        oracle ``measure_plus_np``.
        """
        all_keys = self._fold_keys(key)
        out: Dict[Clique, np.ndarray] = {}
        for tok, cliques in self._measure_groups.items():
            if not tok:
                for c in cliques:
                    out[c] = np.asarray(self._draw_empty(all_keys, c),
                                        np.float64)
                continue
            z = np.asarray(self._draw_group(all_keys,
                                            self._measure_specs[tok]),
                           np.float64)
            for i, c in enumerate(cliques):
                out[c] = z[i]
        return out

    # ------------------------------------------------------------------ serve
    def measure(self, marginals: Mapping[Clique, jnp.ndarray],
                key: jax.Array) -> Dict[Clique, Measurement]:
        """Algorithm 5 over the whole closure, signature-batched on device.

        ``marginals[A]`` must hold the exact marginal table for every A in
        the plan's closure (flattened or tensor shaped).
        """
        self.stats.bump("measure_calls")
        with TRACER.span("engine.measure").set(
                engine="plus", cliques=len(self.plan.cliques),
                use_kernel=self.use_kernel):
            return self._measure_impl(marginals, key)

    def _measure_impl(self, marginals, key):
        all_keys = self._fold_keys(key)
        out: Dict[Clique, Measurement] = {}
        for tok, cliques in self._measure_groups.items():
            if not tok:
                for c in cliques:
                    v = np.asarray(marginals[c], np.float64).reshape(-1)
                    z = np.asarray(self._draw_empty(all_keys, c))
                    s2 = self.plan.sigma2(c)
                    out[c] = Measurement(c, v + math.sqrt(s2) * z, s2)
                continue
            s = self._measure_specs[tok]
            g, m = s["g"], s["m"]
            vs = np.empty((g, m), np.float64)
            for i, c in enumerate(cliques):
                v = np.asarray(marginals[c], np.float64).reshape(-1)
                if v.shape[0] != m:
                    raise ValueError(
                        f"marginal for {c} has {v.shape[0]} cells, want {m}")
                vs[i] = v
            z = self._draw_group(all_keys, s)
            if self.use_kernel:
                om = self._measure_group_kernel(s, jnp.asarray(vs), z)
            else:
                om = s["combine"](jnp.asarray(vs), z)
            om = np.asarray(om)
            for i, c in enumerate(cliques):
                out[c] = Measurement(c, om[i], self.plan.sigma2(c))
        return out

    def _measure_group_kernel(self, s: dict, v_stack, z):
        """Staged Alg 5 through the fused Pallas chains (stats instrumented)."""
        dims, zdims, stage_a, stage_b = s["split"]
        if s["has_a"]:
            v_stack = fused_chain_matvec(stage_a, v_stack, dims)
        x = jnp.concatenate([v_stack.astype(z.dtype), z], axis=0)
        if s["has_b"]:
            x = fused_chain_matvec(stage_b, x, zdims)
        g = s["g"]
        return x[:g] + jnp.asarray(s["sig"], x.dtype) * x[g:]

    def _embed_group(self, measurements: Mapping[Clique, Measurement],
                     group: List[Clique], in_dims: Sequence[int]) -> np.ndarray:
        """Batched Σ_{A'⊆A} e_{A'} embeddings for a whole signature group.

        All cliques of a group share the slot layout (it depends only on the
        per-axis ranks), so each of the 2^k subset patterns is filled with one
        vectorized assignment across the group instead of per clique.
        """
        import itertools
        g, k = len(group), len(in_dims)
        t = np.zeros((g,) + tuple(in_dims), np.float64)
        c0 = group[0]
        for mask in itertools.product((False, True), repeat=k):
            region, shape = subset_slot_region(
                c0, tuple(a for a, inc in zip(c0, mask) if inc), in_dims)
            block = np.empty((g,) + shape, np.float64)
            for i, c in enumerate(group):
                sub = tuple(a for a, inc in zip(c, mask) if inc)
                block[i] = np.asarray(measurements[sub].omega,
                                      np.float64).reshape(shape)
            t[(slice(None),) + region] = block
        return t.reshape(g, -1)

    def reconstruct(self, measurements: Mapping[Clique, Measurement],
                    cliques: Optional[Sequence[Clique]] = None
                    ) -> Dict[Clique, np.ndarray]:
        """Algorithm 6 for the workload (or ``cliques``): one merged chain
        per signature group, with prefix/range W_i applied implicitly."""
        self.stats.bump("reconstruct_calls")
        with TRACER.span("engine.reconstruct").set(
                engine="plus", use_kernel=self.use_kernel):
            return self._reconstruct_impl(measurements, cliques)

    def _reconstruct_impl(self, measurements, cliques=None):
        specs = self._ensure_reconstruct_state()
        if cliques is None:
            groups = self._reconstruct_groups
        else:
            groups = plus_signature_groups(self.schema, cliques)
        out: Dict[Clique, np.ndarray] = {}
        for tok, group in groups.items():
            if not tok:
                for c in group:
                    out[c] = np.asarray(measurements[()].omega,
                                        dtype=float).reshape(-1)
                continue
            s = specs.get(tok)
            if s is None:   # ad-hoc clique outside the workload's signatures
                s = specs[tok] = self._build_reconstruct_spec(group[0])
            x = self._embed_group(measurements, group, s["in_dims"])
            if self.use_kernel:
                y = fused_chain_matvec(s["factors"], jnp.asarray(x),
                                       s["in_dims"], epilogue=s["epilogue"],
                                       allow_narrow=True)
                y = s["expand"](y.reshape((len(group),)
                                          + tuple(s["chain_out"])))
            else:
                y = s["full"](jnp.asarray(x, jnp.float32))
                CHAIN_STATS.inc("epilogue_axes",
                                sum(1 for op in s["epilogue"] if op))
            y = np.asarray(y)
            for i, c in enumerate(group):
                out[c] = y[i]
        return out

    # release()/synthesize() come from ReleaseServing.  Postprocessing and
    # synthesis operate on *marginal tables*: they are available exactly when
    # every attribute basis is the identity (W_i = I, so Alg 6's answers ARE
    # the marginals); generalized range/prefix answers are not a consistent-
    # marginal family and are rejected up front.
    def _check_postprocess(self) -> None:
        bad = [i for i, b in enumerate(self.schema.bases)
               if b.kind != "identity"]
        if bad:
            raise ValueError(
                "postprocess/synthesize require identity-basis marginals; "
                f"attributes {bad} use non-identity bases "
                f"({[self.schema.bases[i].kind for i in bad]})")
