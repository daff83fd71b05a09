"""CompositeEngine: serve a block-decomposed plan through the fused engines.

Measurement and reconstruction dispatch per block to ordinary
:class:`~repro.engine.engine.MarginalEngine` instances obtained through the
sharded engine cache (:func:`repro.engine.sharded._engine_for`), so block
engines are shared across composite engines, sharded calls and repeated
releases — a block planned twice compiles once.

The one cross-block subtlety is the shared empty clique (docs/DESIGN.md
§12): every block closure contains ∅, but the composite charges its pcost
once, so the noisy total is **measured once** (by block 0) and injected into
every other block's measurement dict before reconstruction.  (Later blocks
still draw their own ∅ noise — discarding an unreleased draw costs nothing —
which keeps each block engine's key-fold order, and therefore its released
noise, bit-identical to serving that block standalone.)

Cut-straddling workload cliques are reconstructed by the product-of-blocks
correction: the normalized outer product of their per-block part tables,
``(⊗_p M̂_p) / T̂^{n_parts−1}`` with T̂ the shared noisy total.
"""
from __future__ import annotations

from typing import Dict, List, Mapping, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.domain import Clique
from repro.core.mechanism import Measurement, noise_dtype
from repro.core.partition import ROW_EMPTY
from repro.engine.engine import EngineStats, ReleaseServing
from repro.obs import TRACER


class CompositeEngine(ReleaseServing):
    """Measurement/reconstruction/release for a CompositePlan."""

    def __init__(self, plan, use_kernel: Optional[bool] = None,
                 precompile: bool = True, dtype=None):
        from repro.kernels.kron_matvec._layout import resolve_use_kernel
        self.plan = plan
        self.use_kernel = resolve_use_kernel(use_kernel)
        self.dtype = noise_dtype() if dtype is None else dtype
        self.stats = EngineStats()
        self._engines = [self._child_engine(bp, precompile)
                         for bp in plan.block_plans]
        self.stats.measure_signatures = sum(
            e.stats.measure_signatures for e in self._engines)
        self.stats.reconstruct_signatures = sum(
            e.stats.reconstruct_signatures for e in self._engines)

    def _child_engine(self, block_plan, precompile: bool):
        # Through the sharded engine cache: block engines are shared with
        # sharded_measure and with any other composite over the same blocks.
        from repro.engine.sharded import _engine_for
        return _engine_for(block_plan, self.use_kernel, self.dtype)

    # ------------------------------------------------------------------ serve
    def measure(self, marginals: Mapping[Clique, jnp.ndarray],
                key: jax.Array) -> Dict[Clique, Measurement]:
        """Per-block Algorithm 1; the shared ∅ is block 0's measurement."""
        self.stats.bump("measure_calls")
        keys = jax.random.split(key, len(self._engines))
        out: Dict[Clique, Measurement] = {}
        with TRACER.span("engine.measure").set(
                engine="composite", blocks=len(self._engines),
                use_kernel=self.use_kernel):
            for b, eng in enumerate(self._engines):
                mb = dict(eng.measure(marginals, keys[b]))
                if b > 0:
                    mb[()] = out[()]
                out.update(mb)
        return out

    def _block_tables(self, measurements: Mapping[Clique, Measurement]
                      ) -> List[Dict[Clique, np.ndarray]]:
        """Each block's reconstructed sub-workload (in-block rows + parts)."""
        return [eng.reconstruct(measurements) for eng in self._engines]

    def _assemble(self, block_tables: List[Dict[Clique, np.ndarray]],
                  total: float, cliques: Sequence[Clique]
                  ) -> Dict[Clique, np.ndarray]:
        """Original-workload tables from block tables (+ straddler products)."""
        d = self.plan.decomposition
        dom = d.workload.domain
        rows = {c: r for r, c in enumerate(d.workload.cliques)}
        out: Dict[Clique, np.ndarray] = {}
        for c in cliques:
            r = rows[c]
            b = int(d.row_block[r])
            if b >= 0:
                out[c] = block_tables[b][c]
            elif b == ROW_EMPTY:
                out[c] = np.asarray([total], dtype=float)
            else:
                parts = d.parts_of(r)
                tab = None
                attrs: List[int] = []
                for pb, pc in parts:
                    pt = np.asarray(block_tables[pb][pc], float).reshape(
                        dom.clique_sizes(pc))
                    tab = pt if tab is None else np.multiply.outer(tab, pt)
                    attrs.extend(pc)
                denom = float(total) ** (len(parts) - 1)
                if len(parts) > 1:
                    tiny = np.finfo(np.float64).tiny
                    if abs(denom) < tiny:
                        denom = np.copysign(tiny, denom if denom else 1.0)
                    tab = tab / denom
                perm = np.argsort(np.asarray(attrs))
                out[c] = np.ascontiguousarray(
                    np.transpose(tab, perm)).reshape(-1)
        return out

    def reconstruct(self, measurements: Mapping[Clique, Measurement],
                    cliques: Optional[Sequence[Clique]] = None
                    ) -> Dict[Clique, np.ndarray]:
        """Per-block Algorithm 2, then stitch the original workload's tables."""
        self.stats.bump("reconstruct_calls")
        d = self.plan.decomposition
        total = float(np.asarray(measurements[()].omega,
                                 float).reshape(-1)[0])
        cliques = list(d.workload.cliques if cliques is None else cliques)
        with TRACER.span("engine.reconstruct").set(
                engine="composite", blocks=len(self._engines),
                use_kernel=self.use_kernel):
            return self._assemble(self._block_tables(measurements), total,
                                  cliques)

    # ---------------------------------------------------------------- release
    def release(self, marginals, key, postprocess: Optional[str] = None,
                total: Optional[float] = None, weights=None,
                mw_rounds: int = 0, **post_opts):
        """measure → per-block reconstruct (→ per-block postprocess) → stitch.

        Postprocessing runs the release subsystem independently on each
        block's plan and tables (consistency/non-negativity are per-block
        properties; the blocks only share the total, which ``total=`` pins
        for every block).  Straddler products are rebuilt from the
        *postprocessed* part tables, so ``"nonneg"`` straddler marginals are
        products of non-negative factors — non-negative themselves — and
        ``synthesize`` works end-to-end.
        """
        if postprocess is None:
            meas = self.measure(marginals, key)
            return self.reconstruct(meas), meas
        if weights is not None:
            raise ValueError("per-marginal postprocess weights are not "
                             "supported on a composite plan; postprocess the "
                             "block plans directly instead")
        from repro.release import postprocess_release
        meas = self.measure(marginals, key)
        bt = self._block_tables(meas)
        t_meas = float(np.asarray(meas[()].omega, float).reshape(-1)[0])
        t_pin = t_meas if total is None else float(total)
        post = [postprocess_release(bp, tables, postprocess, total=t_pin,
                                    mw_rounds=mw_rounds, **post_opts)
                for bp, tables in zip(self.plan.block_plans, bt)]
        out = self._assemble(post, t_pin, list(self.plan.workload.cliques))
        self.stats.bump("postprocess_calls")
        if postprocess == "nonneg":
            self._synth_tables = out
        return out, meas

    # ------------------------------------------------------------- introspect
    def variances(self) -> Dict[Clique, float]:
        return self.plan.workload_variances()

    def block_engines(self) -> List:
        """The per-block fused engines (shared via the engine cache)."""
        return list(self._engines)
