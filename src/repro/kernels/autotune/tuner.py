"""Per-signature-group launch-config autotuner for the fused Kron-chain
kernel (docs/DESIGN.md §14, docs/TUNING.md).

For each chain signature group — same per-axis factor shapes, epilogue and
(padded) batch — the tuner enumerates a small candidate lattice of
``(block_l, compute_dtype)`` launch configs, scores each with the analytic
roofline cost model (:class:`repro.roofline.cost_model.CostModel`), and
caches the winner.  Whether the chain fuses at all is not the tuner's
choice: it is the batch-independent footprint rule
(:func:`repro.kernels.kron_matvec.fused.chain_fuses`), so a chain rounds
the same way whatever batch it rides in.  ``REPRO_KERNEL_AUTOTUNE`` selects the mode:

* ``off``     — fixed untuned defaults everywhere (the pre-tuner behavior);
* ``model``   — analytic pick only (the default; zero kernel launches);
* ``measure`` — analytic shortlist refined by on-device timing of the top
  candidates (launches real kernels; used by engine pre-tuning and CI bench).

Winners live in a per-process registry and, when tuned through
:func:`tune_chain`/:func:`pretune` (the engine pre-tuning path), in an
on-disk JSON cache keyed by ``(device_kind, chain signature)`` so serving
restarts skip re-tuning.  On-the-fly resolution inside a kernel call
(:func:`resolve_config` miss) uses the analytic model only and does not
persist — measurement from inside a serving request would stall it.

Mixed-precision candidates (bf16/fp16 operands, fp32 accumulation) are only
enumerated when ``REPRO_KERNEL_COMPUTE_DTYPES`` lists them or a caller asks
explicitly; call sites that carry Gaussian noise clamp narrow configs back
to fp32 (``allow_narrow=False`` in ``fused_chain_matvec``) — noise stays
fp32, only the data path may narrow.
"""
from __future__ import annotations

import os
import time
from dataclasses import asdict, dataclass, replace
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.roofline.cost_model import CostModel, DeviceSpec, detect_device

from .cache import TuningCache

# measure mode: number of analytically best candidates to time for real.
_MEASURE_TOP_K = 3
_MEASURE_REPS = 3

# Largest fused row block the tuner offers on a real chip.
_MAX_CHIP_BLOCK_L = 512


@dataclass(frozen=True)
class TunedConfig:
    """Winner for one chain signature group — what the kernel launches with."""

    block_l: int
    vmem_budget: int
    compute_dtype: str = "float32"
    fused: bool = True               # False: the footprint rule says per-axis
    predicted_s: float = 0.0
    intensity: float = 0.0           # predicted flops / HBM byte
    grid_steps: int = 0
    source: str = "model"            # model | measure | cache | default

    def as_dict(self) -> dict:
        return asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "TunedConfig":
        fields = {f for f in cls.__dataclass_fields__}  # noqa: C416
        return cls(**{k: v for k, v in d.items() if k in fields})


def autotune_mode() -> str:
    m = os.environ.get("REPRO_KERNEL_AUTOTUNE", "model").strip().lower()
    return m if m in ("off", "model", "measure") else "model"


def _dtype_candidates(dtypes: Optional[Sequence[str]]) -> Tuple[str, ...]:
    if dtypes:
        return tuple(dtypes)
    env = os.environ.get("REPRO_KERNEL_COMPUTE_DTYPES", "")
    if env:
        out = tuple(d.strip() for d in env.split(",") if d.strip())
        return out or ("float32",)
    return ("float32",)


def chain_key(device_kind: str, dims: Sequence[int],
              fshapes: Sequence[Optional[Tuple[int, int]]],
              epilogue: Optional[Sequence[Optional[str]]],
              batch: int) -> str:
    """Stable string key for one (device, chain signature, batch) group."""
    f = ",".join("-" if s is None else f"{s[0]}x{s[1]}" for s in fshapes)
    e = ",".join("-" if op is None else str(op)
                 for op in (epilogue or (None,) * len(tuple(dims))))
    d = ",".join(str(int(n)) for n in dims)
    return f"{device_kind}|d={d}|f={f}|e={e}|b={int(batch)}"


# Per-process registry: chain_key -> TunedConfig.  Every resolution path
# lands here so /stats can report the decisions actually in effect.
_REGISTRY: Dict[str, TunedConfig] = {}


def reset_registry() -> None:
    _REGISTRY.clear()


def registry_snapshot() -> dict:
    dev = detect_device()
    return {"mode": autotune_mode(), "device": dev.kind,
            "entries": {k: cfg.as_dict() for k, cfg in _REGISTRY.items()}}


def _fshapes(factors: Sequence, dims: Sequence[int]
             ) -> Tuple[Optional[Tuple[int, int]], ...]:
    from repro.kernels.kron_matvec._layout import normalize_factor
    out = []
    for f, n in zip(factors, dims):
        s = normalize_factor(f, int(n))
        out.append(None if s is None else (int(s.shape[0]), int(s.shape[1])))
    return tuple(out)


def _block_lattice(batch: int, sub: int, max_exact: int) -> List[int]:
    """Candidate block_l values: sublane-multiple powers of two up to the
    padded batch, plus the exact padded batch itself (grid == 1 with zero
    rounding waste — the interpret-mode winner for awkward batch sizes)."""
    from repro.kernels.kron_matvec._layout import pad_to
    b_p = pad_to(max(batch, 1), sub)
    cands = []
    bl = sub
    while bl < min(b_p, max_exact):
        cands.append(bl)
        bl *= 2
    cands.append(min(b_p, max_exact))
    return sorted(set(cands))


def tune_chain(factors: Sequence, dims: Sequence[int], batch: int = 1,
               epilogue: Optional[Sequence[Optional[str]]] = None,
               dtypes: Optional[Sequence[str]] = None,
               device: Optional[DeviceSpec] = None,
               mode: Optional[str] = None,
               persist: bool = True,
               interpret: Optional[bool] = None) -> TunedConfig:
    """Tune ONE chain signature group and register (and persist) the winner.

    ``dtypes`` widens the candidate lattice beyond fp32 (callers opt into
    narrowing; see module docstring).  ``mode`` overrides the env mode —
    ``resolve_config`` passes ``"model"`` for on-the-fly misses.
    """
    from repro.kernels.kron_matvec.fused import (_SUBLANE, chain_fuses,
                                                  plan_chain)

    dev = detect_device() if device is None else device
    mode = autotune_mode() if mode is None else mode
    model = CostModel(dev)
    dims = tuple(int(d) for d in dims)
    fshapes = _fshapes(factors, dims)
    epi = tuple(epilogue) if epilogue is not None else (None,) * len(dims)
    key = chain_key(dev.kind, dims, fshapes, epi, batch)

    # Interpret mode pays per grid step, so any block up to the exact padded
    # batch is a candidate (2**16 rows is far past any signature group).  On
    # a chip Mosaic unrolls a block's contraction, so blocks past 512 rows
    # only add compile time once the step overhead is amortized.
    max_rows = 2 ** 16 if dev.interpret else _MAX_CHIP_BLOCK_L
    scored = []   # (cost, plan)
    for dt in _dtype_candidates(dtypes):
        sub = _SUBLANE.get(dt, 8)
        for bl in _block_lattice(batch, sub, max_exact=max_rows):
            plan = plan_chain(factors, dims, batch=batch, block_l=bl,
                              vmem_budget=dev.vmem_limit, epilogue=epi,
                              compute_dtype=dt)
            if not plan.fused_ok:      # tile would overflow the device ceiling
                continue
            scored.append((model.chain_cost(plan, batch), plan))

    per_axis_s = model.per_axis_cost(dims, fshapes, batch)
    fuses = bool(scored) and chain_fuses(
        factors, dims, epi, scored[0][1].compute_dtype,
        vmem_budget=dev.default_vmem_budget)
    if not fuses:
        from repro.kernels.kron_matvec._layout import pad_to
        cfg = TunedConfig(block_l=min(128, pad_to(max(batch, 1), 8)),
                          vmem_budget=dev.default_vmem_budget,
                          fused=False, predicted_s=per_axis_s,
                          source="model")
        _REGISTRY[key] = cfg
        return cfg

    scored.sort(key=lambda cp: cp[0].predicted_s)
    best_cost, best_plan = scored[0]

    if mode == "measure":
        best_cost, best_plan = _refine_by_timing(
            scored[:_MEASURE_TOP_K], factors, dims, batch, epi, interpret)

    cfg = TunedConfig(block_l=best_plan.block_l,
                      vmem_budget=best_plan.vmem_bytes,
                      compute_dtype=best_plan.compute_dtype, fused=True,
                      predicted_s=best_cost.predicted_s,
                      intensity=best_cost.intensity,
                      grid_steps=best_cost.grid_steps,
                      source="measure" if mode == "measure" else "model")
    _REGISTRY[key] = cfg
    if persist:
        TuningCache(dev.kind).put(key, cfg.as_dict())
    return cfg


def _refine_by_timing(shortlist, factors, dims, batch, epilogue, interpret):
    """Time the analytically-best candidates for real and keep the fastest.

    Every call passes the candidate config EXPLICITLY, which bypasses the
    tuner in ``fused_chain_matvec`` — no recursion, and the measurement
    exercises exactly the launch being scored.
    """
    import jax.numpy as jnp

    from repro.kernels.kron_matvec.fused import fused_chain_matvec

    n_in = int(np.prod([int(d) for d in dims])) if dims else 1
    x = jnp.zeros((max(batch, 1), n_in), jnp.float32)
    best = None
    for cost, plan in shortlist:
        def run(plan=plan):
            fused_chain_matvec(
                factors, x, dims, interpret=interpret,
                block_l=plan.block_l, vmem_budget=plan.vmem_bytes,
                epilogue=plan.epilogue, compute_dtype=plan.compute_dtype,
                allow_narrow=True).block_until_ready()
        try:
            run()                                  # warm the jit cache
            t = min(_timed(run) for _ in range(_MEASURE_REPS))
        except Exception:                          # pragma: no cover - backend
            continue
        # Replace the analytic time with the measured one; keep the rest of
        # the analytic cost fields (intensity etc.) for reporting.
        measured = replace(cost, predicted_s=t)
        if best is None or measured.predicted_s < best[0].predicted_s:
            best = (measured, plan)
    return best if best is not None else shortlist[0]


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def resolve_config(factors: Sequence, dims: Sequence[int], batch: int,
                   epilogue: Optional[Sequence[Optional[str]]] = None,
                   interpret: Optional[bool] = None) -> Optional[TunedConfig]:
    """Tuned config for a chain call, or None when tuning is off.

    Resolution order: env mode gate → per-process registry → on-disk cache →
    on-the-fly analytic tune (model only, not persisted — see module
    docstring).  Called by ``fused_chain_matvec`` only when the caller passed
    no explicit launch kwargs.
    """
    mode = autotune_mode()
    if mode == "off":
        return None
    dev = detect_device()
    dims_t = tuple(int(d) for d in dims)
    fshapes = _fshapes(factors, dims_t)
    epi = tuple(epilogue) if epilogue is not None \
        else (None,) * len(dims_t)
    key = chain_key(dev.kind, dims_t, fshapes, epi, batch)
    cfg = _REGISTRY.get(key)
    if cfg is not None:
        return cfg
    blob = TuningCache(dev.kind).get(key)
    if blob is not None:
        cfg = TunedConfig.from_dict({**blob, "source": "cache"})
        _REGISTRY[key] = cfg
        return cfg
    return tune_chain(factors, dims_t, batch=batch, epilogue=epi,
                      device=dev, mode="model", persist=False,
                      interpret=interpret)


def pretune(chains: Sequence[tuple],
            device: Optional[DeviceSpec] = None,
            mode: Optional[str] = None) -> List[TunedConfig]:
    """Tune a batch of chain groups up front (engine construction path).

    ``chains`` holds ``(factors, dims, batch, epilogue)`` tuples.  Winners
    are persisted to the on-disk cache; in ``measure`` mode this is where
    real kernels get timed, safely outside any serving request.
    """
    out = []
    for factors, dims, batch, epilogue in chains:
        out.append(tune_chain(factors, dims, batch=batch, epilogue=epilogue,
                              device=device, mode=mode, persist=True))
    return out
