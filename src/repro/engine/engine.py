"""MarginalEngine: a plan compiled once, served many times.

The ROADMAP's north star is serving heavy marginal-query traffic; this module
is the seed of that server.  At construction the engine walks the plan's
signature groups (docs/DESIGN.md §4–5), plans every fused kernel chain it will
ever need — the measurement chains ⊗ Sub_{n_i} over the closure and the
reconstruction chains ⊗ T_i over the workload — and warms the jit cache so
that ``measure`` / ``reconstruct`` calls on the hot path never trace or
compile.  The jit cache is keyed on the chain *signature* (per-axis factor
shapes + batch padding), so domains with repeated attribute sizes share
compilations.

Usage::

    engine = MarginalEngine(plan)
    meas   = engine.measure(marginals, key)      # one fused chain per signature
    tables = engine.reconstruct(meas)            # one fused chain per signature
    # or end-to-end (optionally through the release subsystem, §11):
    tables, meas = engine.release(marginals, key, postprocess="nonneg")
    records = engine.synthesize(1_000_000, key2)
"""
from __future__ import annotations

from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.domain import Clique
from repro.core.mechanism import Measurement, measure, signature_groups
from repro.core.reconstruct import reconstruct_all_batched, u_chain_factors
from repro.core.residual import sub_matrix
from repro.core.select import Plan
from repro.kernels.kron_matvec._layout import pad_to
from repro.kernels.kron_matvec.fused import (chain_fuses, fused_chain_matvec,
                                             plan_chain)
from repro.obs import REGISTRY, TRACER, AtomicCounter

# Process-wide aggregate of every EngineStats bump, labeled by counter name —
# the /metrics view of engine activity across all engines in the process
# (per-engine values stay on each EngineStats instance).
_ENGINE_EVENTS = REGISTRY.counter(
    "repro_engine_events_total",
    "Engine counter bumps aggregated across all engines", labels=("counter",))


class EngineStats:
    """Per-engine counters, backed by the obs metrics registry.

    Historically a plain dataclass of ints; engines shared through
    ``EnginePool`` are bumped from the serve worker *and* warmup/HTTP-reader
    paths, so each field is now an :class:`~repro.obs.AtomicCounter`.  Field
    access keeps the dataclass surface (``stats.measure_calls`` reads,
    ``stats.measure_signatures = n`` level-sets), while hot mutation sites
    use :meth:`bump`, which is atomic and mirrors the event into the global
    ``repro_engine_events_total{counter=...}`` family for ``/metrics``.

    Field inventory (docs/DESIGN.md §10/§11/§14):

    * measure/reconstruct_calls, measure/reconstruct_signatures
    * fused_chains / fallback_chains / tuned_chains — chain planning outcome
    * compile_warmups — warmup launches at construction
    * device_h_groups / exact_h_groups / host_y_groups — DiscreteEngine
      exactness boundary
    * postprocess_calls / synthesize_calls — release subsystem
    * cache_hits / cache_misses — sharded engine-cache provenance
    """

    _FIELDS = (
        "measure_calls", "reconstruct_calls",
        "measure_signatures", "reconstruct_signatures",
        "fused_chains", "fallback_chains", "compile_warmups", "tuned_chains",
        "device_h_groups", "exact_h_groups", "host_y_groups",
        "postprocess_calls", "synthesize_calls",
        "cache_hits", "cache_misses",
    )

    __slots__ = ("_cells",)

    def __init__(self, **initial):
        self._cells = {f: AtomicCounter(initial.pop(f, 0))
                       for f in self._FIELDS}
        if initial:
            raise TypeError(f"unknown EngineStats fields: {tuple(initial)}")

    def bump(self, name: str, n: int = 1) -> None:
        """Atomically increment ``name`` and mirror it to /metrics."""
        self._cells[name].inc(n)
        _ENGINE_EVENTS.labels(counter=name).inc(n)

    def to_dict(self) -> Dict[str, int]:
        return {f: int(self._cells[f].value) for f in self._FIELDS}

    def __repr__(self) -> str:
        body = ", ".join(f"{k}={v}" for k, v in self.to_dict().items())
        return f"EngineStats({body})"

    def __eq__(self, other) -> bool:
        if isinstance(other, EngineStats):
            return self.to_dict() == other.to_dict()
        return NotImplemented


def _stats_field(name: str) -> property:
    def _get(self) -> int:
        return int(self._cells[name].value)

    def _set(self, v: int) -> None:
        self._cells[name].set(v)

    return property(_get, _set)


for _f in EngineStats._FIELDS:
    setattr(EngineStats, _f, _stats_field(_f))
del _f


class ChainRegistry:
    """Chain-plan bookkeeping shared by MarginalEngine and PlusEngine.

    One definition of the plan key (dims, signature, padded batch) and the
    layout report keeps the two engines' stats and warmup coverage in exact
    agreement.  Subclasses provide ``self.stats`` (EngineStats) and their own
    warmup loops over ``self._chain_plans``, whose values are
    ``(ChainPlan, factors, batch, epilogue)`` tuples.

    Registration is where the autotuner hooks in (docs/DESIGN.md §14): when
    ``REPRO_KERNEL_AUTOTUNE`` is not ``off``, every chain group is tuned up
    front — in ``measure`` mode this times real kernels, safely outside any
    serving request — and the plan row reflects the tuned launch config the
    serving path will resolve.  ``role`` tags the chain's serving duty:
    ``"measure"`` chains carry Gaussian noise lanes and are always planned at
    float32; ``"reconstruct"`` chains may adopt a tuned narrow compute dtype
    (fp32 accumulation) when one is enabled.
    """

    _chain_plans: Dict[tuple, tuple]
    _chain_tune: Dict[tuple, object]
    _chain_roles: Dict[tuple, str]

    def _register_chain(self, factors: List, dims: Tuple[int, ...],
                        batch: int, epilogue: Optional[tuple] = None,
                        role: str = "measure") -> None:
        from repro.kernels.autotune import autotune_mode, tune_chain
        cfg = None
        if autotune_mode() != "off":
            cfg = tune_chain(factors, dims, batch=batch, epilogue=epilogue)
            dt = cfg.compute_dtype if role == "reconstruct" else "float32"
            cp = plan_chain(factors, dims, batch=batch, block_l=cfg.block_l,
                            vmem_budget=cfg.vmem_budget, epilogue=epilogue,
                            compute_dtype=dt)
        else:
            cp = plan_chain(factors, dims, batch=batch, epilogue=epilogue)
        fused = chain_fuses(factors, dims, epilogue, cp.compute_dtype)
        key = (tuple(dims), cp.signature, pad_to(batch, cp.block_l))
        if key not in self._chain_plans:
            self._chain_plans[key] = (cp, factors, batch, epilogue)
            if not hasattr(self, "_chain_tune"):
                self._chain_tune = {}
                self._chain_roles = {}
            self._chain_tune[key] = cfg
            self._chain_roles[key] = role
            if fused:
                self.stats.bump("fused_chains")
            else:
                self.stats.bump("fallback_chains")
            if cfg is not None:
                self.stats.bump("tuned_chains")
            self._publish_roofline(key, cp, batch)

    def _publish_roofline(self, key: tuple, cp, batch: int) -> None:
        """Export the chain's roofline predictions as gauges.

        Predicted arithmetic intensity, VMEM footprint, and runtime
        (roofline/cost_model.py) sit next to the measured
        ``repro_kernel_launch_seconds`` histogram under the same ``chain``
        label, so predicted-vs-measured drift is a single /metrics query.
        """
        try:
            from repro.obs.naming import chain_label
            from repro.roofline.cost_model import CostModel
            cost = CostModel().chain_cost(cp, batch)
            label = chain_label(key[0], batch, cp.compute_dtype)
            REGISTRY.gauge(
                "repro_chain_predicted_intensity",
                "Roofline-predicted arithmetic intensity (FLOP/byte)",
                labels=("chain",)).labels(chain=label).set(cost.intensity)
            REGISTRY.gauge(
                "repro_chain_vmem_bytes",
                "Planned VMEM footprint of the fused chain kernel",
                labels=("chain",)).labels(chain=label).set(cp.vmem_bytes)
            REGISTRY.gauge(
                "repro_chain_predicted_seconds",
                "Roofline-predicted single-launch runtime",
                labels=("chain",)).labels(chain=label).set(cost.predicted_s)
        except Exception:   # cost model is advisory; never fail registration
            pass

    def _chain_allow_narrow(self, key: tuple) -> bool:
        """Reconstruct-role chains may serve at a tuned narrow dtype."""
        return getattr(self, "_chain_roles", {}).get(key) == "reconstruct"

    def chain_plans(self) -> List[dict]:
        """Layout report: one row per compiled chain (for ops/debugging)."""
        rows = []
        tune = getattr(self, "_chain_tune", {})
        for key, (cp, factors, batch, epilogue) in self._chain_plans.items():
            dims, _sig, b_p = key
            cfg = tune.get(key)
            rows.append(dict(dims=dims, batch=batch, batch_padded=b_p,
                             w_in=cp.w_in, w_out=cp.w_out, block_l=cp.block_l,
                             vmem_bytes=cp.vmem_bytes,
                             fused=chain_fuses(factors, dims, epilogue,
                                               cp.compute_dtype),
                             epilogue=cp.epilogue,
                             compute_dtype=cp.compute_dtype,
                             tuned=cfg is not None,
                             tune_source=cfg.source if cfg else "default",
                             intensity=cfg.intensity if cfg else None))
        return rows


class ReleaseServing:
    """release/postprocess/synthesize surface shared by all serving engines.

    ``release(..., postprocess="consistent"|"nonneg")`` routes the raw
    reconstruction through :mod:`repro.release` (docs/DESIGN.md §11):
    covariance-weighted consistency (precision weights straight off the
    plan's IR) and, for ``"nonneg"``, the signature-batched simplex
    projection with exact total preservation.  ``synthesize`` samples
    records from the last non-negative release (or explicit ``tables``).
    Engines override ``_postprocess_total`` (the secure path pins the
    measured integer total) and ``_check_postprocess`` (RP+ restricts to
    identity-basis schemas).
    """

    _synth_tables: Optional[Dict[Clique, np.ndarray]] = None

    def _postprocess_total(self, measurements) -> Optional[float]:
        """Total-count pin for the consistency fit (None: fit it)."""

    def _check_postprocess(self) -> None:
        """Raise when this plan family's tables are not plain marginals."""

    def release(self, marginals, key, postprocess: Optional[str] = None,
                total: Optional[float] = None, weights=None,
                mw_rounds: int = 0, **post_opts):
        """measure → reconstruct (→ postprocess); returns (tables, meas).

        ``postprocess=None`` is the historical raw unbiased release;
        ``"consistent"`` / ``"nonneg"`` run the release subsystem with
        ``total``/``weights``/``mw_rounds`` forwarded to
        :func:`repro.release.postprocess_release`.
        """
        meas = self.measure(marginals, key)
        tables = self.reconstruct(meas)
        if postprocess is not None:
            self._check_postprocess()
            from repro.release import postprocess_release
            if total is None:
                total = self._postprocess_total(meas)
            tables = postprocess_release(self.plan, tables, postprocess,
                                         total=total, weights=weights,
                                         mw_rounds=mw_rounds, **post_opts)
            self.stats.bump("postprocess_calls")
            if postprocess == "nonneg":
                self._synth_tables = tables
        return tables, meas

    def synthesize(self, n_records: int, key, tables=None, order=None,
                   batch: Optional[int] = None) -> np.ndarray:
        """Sample (n_records, n_attrs) synthetic records from the marginals.

        ``tables=None`` uses the engine's last ``postprocess="nonneg"``
        release; junction-order conditional sampling is fully vectorized
        (:func:`repro.release.synthesize_records`) and never touches the
        contingency table.
        """
        if tables is None:
            tables = self._synth_tables
            if tables is None:
                raise ValueError(
                    "no non-negative release to sample from: call "
                    "release(..., postprocess=\"nonneg\") first or pass "
                    "tables=")
        from repro.release import synthesize_records
        self.stats.bump("synthesize_calls")
        return synthesize_records(self.plan.domain, tables, n_records, key,
                                  order=order, batch=batch)


class MarginalEngine(ReleaseServing, ChainRegistry):
    """Compile a plan's kernel chains once; serve measure/reconstruct traffic.

    Parameters
    ----------
    plan:        selection-phase output (σ²_A per closure clique).
    use_kernel:  route chains through the fused Pallas kernel or the batched
                 jnp path (still signature-batched, no pallas_call).  The
                 default ``None`` resolves per backend — Pallas on TPU,
                 batched jnp elsewhere, where interpret-mode kernels would
                 only add Python overhead.
    precompile:  trace/compile every chain at construction so serving calls
                 are cache hits (set False for tiny one-shot jobs).
    dtype:       noise-draw dtype; ``None`` resolves to
                 :func:`repro.core.mechanism.noise_dtype`.
    """

    def __init__(self, plan: Plan, use_kernel: Optional[bool] = None,
                 precompile: bool = True, dtype=None):
        from repro.core.mechanism import noise_dtype
        from repro.kernels.kron_matvec._layout import resolve_use_kernel
        self.plan = plan
        self.use_kernel = resolve_use_kernel(use_kernel)
        self.dtype = noise_dtype() if dtype is None else dtype
        self.stats = EngineStats()
        self._measure_groups = signature_groups(plan.domain, plan.cliques)
        self._reconstruct_groups = signature_groups(plan.domain,
                                                    plan.workload.cliques)
        self.stats.measure_signatures = len(self._measure_groups)
        self.stats.reconstruct_signatures = len(self._reconstruct_groups)
        self._chain_plans: Dict[tuple, object] = {}
        for dims, cliques in self._measure_groups.items():
            if dims:
                self._register_chain([sub_matrix(n) for n in dims], dims,
                                     2 * len(cliques), role="measure")
        for dims, cliques in self._reconstruct_groups.items():
            if dims:
                self._register_chain(
                    u_chain_factors(plan.domain, cliques[0]), dims,
                    len(cliques), role="reconstruct")
        if precompile and self.use_kernel:
            self._warmup()

    def _warmup(self) -> None:
        """Run every planned chain once on zeros — fills the pallas/jit cache
        for the exact batch paddings the serving path will request."""
        for key, (cp, factors, batch, _epi) in self._chain_plans.items():
            dims = key[0]
            x = jnp.zeros((batch, cp.n_in), jnp.float32)
            fused_chain_matvec(
                factors, x, dims,
                allow_narrow=self._chain_allow_narrow(key)).block_until_ready()
            self.stats.bump("compile_warmups")

    # ------------------------------------------------------------------ serve
    def measure(self, marginals: Mapping[Clique, jnp.ndarray],
                key: jax.Array) -> Dict[Clique, Measurement]:
        """Algorithm 1 over the whole closure: one fused chain per signature."""
        self.stats.bump("measure_calls")
        with TRACER.span("engine.measure").set(
                engine="marginal", cliques=len(self.plan.cliques),
                use_kernel=self.use_kernel):
            return measure(self.plan, marginals, key,
                           use_kernel=self.use_kernel, batched=True,
                           dtype=self.dtype)

    def reconstruct(self, measurements: Mapping[Clique, Measurement],
                    cliques: Optional[Sequence[Clique]] = None
                    ) -> Dict[Clique, np.ndarray]:
        """Algorithm 2 for the workload (or ``cliques``): batched merged chains."""
        self.stats.bump("reconstruct_calls")
        with TRACER.span("engine.reconstruct").set(
                engine="marginal", use_kernel=self.use_kernel):
            return reconstruct_all_batched(self.plan, measurements, cliques,
                                           use_kernel=self.use_kernel)

    # release()/synthesize() come from ReleaseServing (postprocess-aware).

    # ------------------------------------------------------------- introspect
    def variances(self) -> Dict[Clique, float]:
        return self.plan.workload_variances()
