"""Declarative rule registries: what counts as a source, sanitizer, sink,
charge, or device limit.

The passes are generic dataflow machines; everything repo-specific lives
here so adding a rule (or pointing the analyzer at a different codebase) is
a registry edit, not a pass rewrite (docs/ANALYSIS.md §How to add a rule).

Name matching is by *last dotted component* — ``self.ledger.charge`` and
``ledger.charge`` both match ``charge`` — which is the right granularity
for an intraprocedural analysis that cannot resolve imports.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, FrozenSet, Optional, Tuple

# Rule catalog (docs/ANALYSIS.md mirrors this — keep the two in sync).
ALL_RULES: Dict[str, str] = {
    "PF001": "raw-data taint reaches a release sink without a noise "
             "sanitizer on the path",
    "PF002": "measurement in a serve-scope class is not dominated by a "
             "budget-ledger charge (charge-before-measure)",
    "KN001": "literal block_l is not a positive multiple of the sublane "
             "quantum for the chain's compute dtype",
    "KN002": "literal vmem_budget exceeds every real accelerator's VMEM "
             "ceiling in the DeviceSpec table",
    "KN003": "narrow compute dtype / allow_narrow=True on a chain inside a "
             "noise-drawing function (noise must stay float32)",
    "KN004": "host side effect (RNG, clock, I/O, env) inside a jitted or "
             "Pallas kernel body",
    "KN005": "literal BlockSpec minor dimension is not a multiple of the "
             "lane quantum (128)",
    "LK001": "field annotated '# guarded-by: <lock>' accessed outside a "
             "'with self.<lock>' block",
    "LK002": "'# guarded-by:' names a lock never created in this class",
    "LINT000": "file could not be parsed",
}


@dataclass(frozen=True)
class PrivacyRegistry:
    """Source/sanitizer/sink vocabulary for the privacy-flow pass."""

    # Calls whose RESULT is raw (pre-noise) data.
    source_calls: FrozenSet[str] = frozenset({
        "exact_marginals_from_x", "sharded_marginals", "_local_marginal",
        "_local_marginals", "marginals_from_records", "synthetic_records",
    })
    # Attribute reads that yield raw data wherever they appear
    # (request payloads: ``req.marginals``, ``req.records``; a tenant's
    # resident ``RecordStore``, read as ``sess.records``).
    source_attrs: FrozenSet[str] = frozenset({"marginals", "records"})
    # Parameters of these names are raw on entry (data-plane helpers).
    source_params: FrozenSet[str] = frozenset({"records", "marginals"})
    # Calls whose result is differentially private — taint stops here.
    sanitizer_calls: FrozenSet[str] = frozenset({
        "measure", "measure_multi", "measure_np", "measure_np_batched",
        "measure_discrete", "sharded_measure", "release",
        "corpus_marginal_release",
    })
    # Metadata projections: shape-class information, not data.
    declassifier_attrs: FrozenSet[str] = frozenset({
        "size", "shape", "ndim", "dtype", "nbytes", "itemsize",
    })
    declassifier_calls: FrozenSet[str] = frozenset({
        "len", "isinstance", "type", "id", "hash",
    })
    # Sinks: raw taint must never reach these (checked everywhere).
    sink_calls: FrozenSet[str] = frozenset({
        "set_result", "set_exception", "_append",
    })
    # Sinks only enforced inside serve-scope modules (response assembly).
    serve_sink_calls: FrozenSet[str] = frozenset({
        "dumps", "write", "sendall",
    })
    # Constructors whose fields ship to tenants.
    sink_constructors: FrozenSet[str] = frozenset({"ReleaseResult"})
    # PF002 protocol vocabulary.
    charge_calls: FrozenSet[str] = frozenset({"charge"})
    measure_calls: FrozenSet[str] = frozenset({"measure", "measure_multi"})
    serve_scope: str = "serve"


DEFAULT_PRIVACY = PrivacyRegistry()


@dataclass(frozen=True)
class KernelLimits:
    """Launch-config constants the kernel-invariant pass enforces.

    Sourced live from :mod:`repro.kernels.kron_matvec.fused` and the
    :mod:`repro.roofline.cost_model` DeviceSpec table so the analyzer can
    never drift from the kernels it checks; the literals below are only the
    fallback when the package is analyzed from a checkout where those
    imports are unavailable.
    """

    sublane: Tuple[Tuple[str, int], ...] = (
        ("float32", 8), ("bfloat16", 16), ("float16", 16))
    lane: int = 128
    # Largest VMEM ceiling across real (non-interpret) accelerators: a
    # literal budget above this cannot fit ANY device in the table.
    vmem_limit_real: int = 32 * 1024 * 1024
    narrow_dtypes: FrozenSet[str] = frozenset({"bfloat16", "float16"})
    chain_calls: FrozenSet[str] = frozenset({
        "plan_chain", "fused_chain_matvec", "tune_chain"})
    noise_calls: FrozenSet[str] = frozenset({
        "normal", "standard_normal", "sample", "sample_discrete_gaussian"})
    host_effect_exact: FrozenSet[str] = frozenset({
        "print", "open", "input", "breakpoint"})
    host_effect_prefixes: Tuple[str, ...] = (
        "np.random.", "numpy.random.", "random.", "os.", "time.", "sys.")

    def sublane_for(self, dtype: str) -> int:
        return dict(self.sublane).get(dtype, 8)


_LIMITS: Optional[KernelLimits] = None


def kernel_limits() -> KernelLimits:
    """KernelLimits bound to the live kernel/cost-model constants."""
    global _LIMITS
    if _LIMITS is not None:
        return _LIMITS
    try:
        from repro.kernels.kron_matvec.fused import _LANE, _SUBLANE
        from repro.roofline.cost_model import DEVICE_TABLE
        vmem = max(spec.vmem_limit for spec in DEVICE_TABLE.values()
                   if not spec.interpret)
        _LIMITS = KernelLimits(
            sublane=tuple(sorted(_SUBLANE.items())), lane=_LANE,
            vmem_limit_real=vmem)
    except Exception:                      # pragma: no cover - no jax runtime
        _LIMITS = KernelLimits()
    return _LIMITS
