r"""Fused multi-axis Pallas kernel: a whole Kronecker factor chain per call.

The per-axis kernel (kron_matvec.py) pays a full zero-pad → HBM round-trip →
slice for every factor of ``⊗_i S_i``.  This module runs the *entire* chain
as ONE ``pallas_call`` (docs/DESIGN.md §3.3):

  * the chain is one dense operator ``K = ⊗_i S_iᵀ`` of shape
    ``(Π n_i, Π m_i)``, built once per distinct chain on the host in float64,
    zero-padded to lane multiples ``(W_in, W_out)`` and kept on the device;
    cumsum epilogues fold into their axis' factor (``cumsum(S_i, axis=0)``);
  * the launch is a tiled matmul ``x @ K`` of the batch stack B (stacked
    [v; z] pairs, stacked same-signature cliques — docs/DESIGN.md §4): the
    grid walks (row, output-column, contraction) blocks and each step is
    one 2-D MXU ``dot_general`` accumulated in the fp32 output block — no
    in-kernel reshape, transpose or scatter, so the body lowers on Mosaic
    at any signature, and small blocks keep its compile time short;
  * exactly one zero-pad on entry (B → B_p, flat width N → W_in) and one
    slice on exit (docs/DESIGN.md §3.4); the pad/slice/pallas_call counts are
    instrumented in stats.py so tests can assert the contract.

The dense operator trades FLOPs (``W_in · W_out`` MACs per row instead of
``Σ_i m_i · Π n``) for a layout the MXU takes as is.  Its bytes are part of
the VMEM footprint, so chains whose operator does not fit the budget — the
large attribute products — fall back to the per-axis kernel (ops.py), which
tiles R and is correct at any size.  Fused versus per-axis is decided by the
footprint (``plan_chain(...).fused_ok``) or by the autotuner's cost model,
never by a flag.

Launch configs are dtype-aware (compute dtype ∈ {float32, bfloat16, float16}
with fp32 accumulation; float32 operands contract at
``Precision.HIGHEST`` so counts and noise keep fp32 accuracy on the MXU), and
when the per-signature autotuner is enabled (``REPRO_KERNEL_AUTOTUNE``,
docs/TUNING.md) ``fused_chain_matvec`` resolves the tuned ``(block_l,
vmem_budget, compute_dtype, fused)`` config for the chain signature instead
of the fixed default (docs/DESIGN.md §14).  Explicitly passed config kwargs
always win and bypass the tuner (that is also how the tuner's own measured
refinement calls avoid recursion).

Validated in interpret mode on CPU against the float64 numpy oracle
(core.kron.kron_matvec_np); on TPU backends the real Mosaic lowering is used.
"""
from __future__ import annotations

import math
import time
from dataclasses import dataclass, field
from functools import lru_cache
from typing import List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.obs import REGISTRY, TRACER
from repro.obs.naming import chain_label

from ._layout import interpret_default as _interpret_default
from ._layout import normalize_factor as _normalize_factor
from ._layout import pad_to as _pad_to
from .stats import CHAIN_STATS

# Measured dispatch time per chain launch, labeled like the roofline gauges
# (obs/naming.py) so predicted-vs-measured is one /metrics join.  Host-side
# dispatch timing: JAX execution is async, so this bounds launch overhead and
# any synchronous work, not device busy time.
_LAUNCH_SECONDS = REGISTRY.histogram(
    "repro_kernel_launch_seconds",
    "Host-side dispatch time of one kron-chain launch",
    labels=("chain",),
    buckets=(1e-5, 3e-5, 1e-4, 3e-4, 1e-3, 3e-3, 1e-2, 3e-2, 0.1, 0.3, 1.0))

_LANE = 128          # minor-axis (lane) padding quantum
_SUB = 8             # sublane padding quantum (float32)
_MAX_BLOCK_L = 128   # batch rows per grid step (untuned default)
_MIB = 1024 * 1024
# Untuned CPU/interpret budget: the v5e default, so interpret-mode runs plan
# the same fused/per-axis split the chip does.
_VMEM_BUDGET = 8 * _MIB

# Sublane quantum per compute dtype (pallas guide: min tile second-to-last
# dim is 8 for fp32, 16 for bf16/fp16).
_SUBLANE = {"float32": 8, "bfloat16": 16, "float16": 16}
_ACC_BYTES = 4       # accumulation / output dtype is always float32


def _sublane(compute_dtype: str) -> int:
    return _SUBLANE.get(str(compute_dtype), _SUB)


def default_vmem_budget() -> int:
    """Device-derived untuned budget: 8 MiB on CPU/interpret (the v5e
    default), the device table's conservative budget on real accelerators."""
    from repro.roofline.cost_model import detect_device
    dev = detect_device()
    return _VMEM_BUDGET if dev.interpret else dev.default_vmem_budget


def contraction_precision(compute_dtype) -> Optional[jax.lax.Precision]:
    """``HIGHEST`` for float32 operands (a full-precision MXU pass instead of
    the reduced-precision default), the default for narrow operands."""
    return (jax.lax.Precision.HIGHEST
            if jnp.dtype(compute_dtype) == jnp.float32 else None)


@dataclass(frozen=True)
class ChainPlan:
    """Static layout plan for one fused chain (docs/DESIGN.md §3.3).

    The plan is the jit-cache key: chains with the same signature — per-axis
    (m_i, n_i) shapes, batch padding and tile widths, compute dtype — share
    one compiled kernel regardless of the factor *values*.
    """

    in_dims: Tuple[int, ...]                       # per-axis input sizes n_i
    fshapes: Tuple[Optional[Tuple[int, int]], ...]  # (m_i, n_i) or None (identity)
    out_dims: Tuple[int, ...]                      # per-axis output sizes
    n_in: int                                      # prod(in_dims)
    n_out: int                                     # prod(out_dims)
    w_in: int                                      # lane-padded input width
    w_out: int                                     # lane-padded output width
    block_l: int                                   # batch rows per grid step
    # The footprint and budget verdict are not part of the compile key.
    vmem_bytes: int = field(compare=False)         # kernel VMEM footprint
    fused_ok: bool = field(compare=False)          # fits the VMEM budget?
    epilogue: Tuple[Optional[str], ...] = ()       # per-axis implicit-W op
    compute_dtype: str = "float32"                 # operand dtype (fp32 accum)
    block_k: int = 0                               # contraction block (lanes)
    block_n: int = 0                               # output-column block

    @property
    def signature(self) -> tuple:
        return (self.in_dims, self.fshapes, self.block_l, self.epilogue,
                self.compute_dtype, self.block_k, self.block_n)

    @property
    def vmem_limit_bytes(self) -> int:
        """Scoped-VMEM limit the kernel is compiled with: its blocks plus
        room for Mosaic's own temporaries, never below the 16 MiB default."""
        blocks = _block_bytes(self.block_l, self.block_k, self.block_n,
                              self.compute_dtype)
        return max(16 * _MIB, _pad_to(2 * blocks, _MIB))


def _chip_blocks(w_in: int, w_out: int) -> Tuple[int, int]:
    """Lane blocks on a chip: the widest of 512/256/128 dividing W_in for
    the contraction, 256 or 128 dividing W_out for the output columns."""
    bk = next(c for c in (512, 256, 128) if w_in % c == 0)
    bn = next(c for c in (256, 128) if w_out % c == 0)
    return bk, bn


def _block_bytes(block_l: int, block_k: int, block_n: int,
                 compute_dtype: str) -> int:
    """VMEM of the kernel's double-buffered input, operator and output
    blocks."""
    isz = jnp.dtype(compute_dtype).itemsize
    return 2 * (isz * block_k * (block_l + block_n)
                + _ACC_BYTES * block_l * block_n)


def plan_chain(factors: Sequence, dims: Sequence[int], batch: int = 1,
               block_l: Optional[int] = None,
               vmem_budget: Optional[int] = None,
               epilogue: Optional[Sequence[Optional[str]]] = None,
               compute_dtype: str = "float32",
               lane_tiles: Optional[bool] = None) -> ChainPlan:
    """Plan the fused layout of ``(⊗_i factors[i])`` applied to a (batch, N) stack.

    ``epilogue[i]`` is an optional shape-preserving implicit-W op applied to
    axis i after the chain: ``'cumsum'`` (prefix-sum along the axis, the
    implicit form of the lower-triangular prefix matrix — docs/DESIGN.md §8).
    It folds into the dense operator, so it costs no VMEM of its own.

    ``compute_dtype`` narrows the *operands* (input tile + operator); the
    contraction still accumulates in float32 (``preferred_element_type``) and
    the output tile is float32.  The footprint is itemsize-correct: the
    ``(W_in, W_out)`` operator and the double-buffered input and operator
    blocks at the compute dtype's itemsize, the double-buffered output block
    at the fp32 accumulator width.  ``vmem_budget=None`` resolves to the
    device default — 8 MiB on CPU/interpret.

    ``lane_tiles`` picks the execution blocks: on a chip (the default off
    interpret mode) the operator streams in 512/256/128-lane blocks, which
    keeps each compiled kernel small; in interpret mode every grid step is
    a Python pass, so the operator is one block.  The footprint always
    counts the chip's blocks, so both plan the same fused/per-axis split.
    """
    compute_dtype = str(jnp.dtype(compute_dtype).name)
    if compute_dtype not in _SUBLANE:
        raise ValueError(f"unsupported compute dtype {compute_dtype!r}; "
                         f"expected one of {sorted(_SUBLANE)}")
    if vmem_budget is None:
        vmem_budget = default_vmem_budget()
    dims = tuple(int(d) for d in dims)
    epilogue = tuple(epilogue) if epilogue is not None else (None,) * len(dims)
    if len(epilogue) != len(dims):
        raise ValueError(f"epilogue length {len(epilogue)} != {len(dims)} axes")
    if any(op not in (None, "cumsum") for op in epilogue):
        raise ValueError(f"unknown epilogue op in {epilogue}")
    specs: List[Optional[Tuple[int, int]]] = []
    out_dims: List[int] = []
    for f, n in zip(factors, dims):
        s = _normalize_factor(f, n)
        if s is None:
            specs.append(None)
            out_dims.append(n)
        else:
            if s.shape[1] != n:
                raise ValueError(f"factor {s.shape} does not match axis size {n}")
            specs.append((int(s.shape[0]), n))
            out_dims.append(int(s.shape[0]))
    n_in = math.prod(dims) if dims else 1
    n_out = math.prod(out_dims) if out_dims else 1
    sub = _sublane(compute_dtype)
    if block_l is None:
        block_l = min(_MAX_BLOCK_L, _pad_to(max(batch, 1), sub))
    block_l = _pad_to(int(block_l), sub)
    w_in = _pad_to(n_in, _LANE)
    w_out = _pad_to(n_out, _LANE)
    bk, bn = _chip_blocks(w_in, w_out)
    # The whole operator counts, though the kernel streams it in blocks: its
    # size is what bounds the dense contraction's FLOP and HBM blow-up.
    vmem = (jnp.dtype(compute_dtype).itemsize * w_in * w_out
            + _block_bytes(block_l, bk, bn, compute_dtype))
    if lane_tiles is None:
        lane_tiles = not _interpret_default()
    if not lane_tiles:
        bk, bn = w_in, w_out
    return ChainPlan(dims, tuple(specs), tuple(out_dims), n_in, n_out,
                     w_in, w_out, block_l, vmem, vmem <= vmem_budget,
                     epilogue, compute_dtype, bk, bn)


def _dense_operator(plan: ChainPlan, live: Sequence[np.ndarray]) -> jax.Array:
    """``⊗_i S_iᵀ`` (cumsum epilogues folded in), padded to (W_in, W_out),
    on the device — built once per distinct chain and cached."""
    return _operator_cached(plan.in_dims, plan.fshapes, plan.epilogue,
                            plan.compute_dtype,
                            tuple(np.ascontiguousarray(f, np.float32).tobytes()
                                  for f in live))


@lru_cache(maxsize=256)
def _operator_cached(in_dims, fshapes, epilogue, compute_dtype,
                     factor_bytes) -> jax.Array:
    """Host float64 Kronecker product, rounded once to the compute dtype.

    Built on the host: the device would need an N-D relayout of the product
    that costs seconds of compile per signature.  Identity axes contribute
    ``I_n``.  Keyed on the factor bytes, so chains that share a signature but
    not their values (RP+ bases) get their own operator.
    """
    raw = iter(factor_bytes)
    k = np.ones((1, 1))
    for n, spec, op in zip(in_dims, fshapes, epilogue):
        s = (np.eye(n) if spec is None else
             np.frombuffer(next(raw), np.float32).reshape(spec)
             .astype(np.float64))
        if op == "cumsum":
            s = np.cumsum(s, axis=0)
        k = np.kron(k, s.T)
    n_in, n_out = k.shape
    k = np.pad(k, ((0, _pad_to(n_in, _LANE) - n_in),
                   (0, _pad_to(n_out, _LANE) - n_out)))
    return jnp.asarray(k, dtype=compute_dtype)


def _make_fused_kernel(plan: ChainPlan):
    """Kernel body: one (block_l, block_k) × (block_k, block_n) MXU
    contraction, accumulated in the fp32 output block across the k axis."""
    precision = contraction_precision(plan.compute_dtype)

    def kernel(x_ref, k_ref, o_ref):
        @pl.when(pl.program_id(2) == 0)
        def _init():
            o_ref[...] = jnp.zeros_like(o_ref)

        o_ref[...] += jax.lax.dot_general(
            x_ref[...], k_ref[...],
            dimension_numbers=(((1,), (0,)), ((), ())),
            precision=precision,
            preferred_element_type=jnp.float32)

    return kernel


def _fused_body(plan: ChainPlan, b: int, b_p: int, interpret: bool):
    """The fused launch as a traceable function ``(K, x) → y``: pad → one
    pallas_call → slice.

    The grid is (row blocks, output-column blocks, contraction blocks); the
    contraction axis is innermost, so every output block sums its partial
    products in one fixed order whatever the batch — rows stay independent
    and a lane's result does not depend on what else shares its launch.
    """
    kernel = _make_fused_kernel(plan)
    bm, bk, bn = plan.block_l, plan.block_k, plan.block_n
    grid = (b_p // bm, plan.w_out // bn, plan.w_in // bk)
    extra = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=plan.vmem_limit_bytes)}

    def call(k, x):
        # ONE pad: batch to the block grid, flat width to the lane grid; the
        # tile narrows to the compute dtype here so VMEM sees planned bytes.
        x_p = jnp.pad(x.astype(plan.compute_dtype),
                      ((0, b_p - b), (0, plan.w_in - plan.n_in)))
        out = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
                      pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j))],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
            out_shape=jax.ShapeDtypeStruct((b_p, plan.w_out), jnp.float32),
            interpret=interpret,
            name="kron_chain_fused",
            **extra,
        )(x_p, k)
        # ONE slice back to the true (B, n_out) extent.
        return out[:b, :plan.n_out]

    return call


def chain_fuses(factors: Sequence, dims: Sequence[int],
                epilogue: Optional[Sequence[Optional[str]]] = None,
                compute_dtype: str = "float32",
                vmem_budget: Optional[int] = None) -> bool:
    """Fused or per-axis, from the chain's footprint alone: the dense
    operator plus the smallest row block must fit the VMEM budget.

    The answer does not depend on the batch, so a chain takes the same path
    — and rounds the same way — whatever shares its launch: fused serving
    stays bit-equal to per-request serving.
    """
    cd = str(jnp.dtype(compute_dtype).name)
    return plan_chain(factors, dims, batch=1, block_l=_sublane(cd),
                      vmem_budget=vmem_budget, epilogue=epilogue,
                      compute_dtype=cd).fused_ok


# Distinct (chain signature, batch, path) launched in this process: each is
# one compiled chain.
_LAUNCHED: set = set()


def launched_chains() -> dict:
    """How many distinct chains this process has launched, by path."""
    fused = sum(1 for *_k, f in set(_LAUNCHED) if f)
    return {"fused": fused, "per_axis": len(_LAUNCHED) - fused}


@dataclass(frozen=True)
class ChainLaunch:
    """One chain, planned and ready to trace into a program.

    The fused path takes the stack flat, ``(B, Π n_i) → (B, Π m_i)``; the
    per-axis path takes it N-D, ``(B, n_1, …, n_k) → (B, m_1, …, m_k)``
    (ops.py).  ``operands`` — the dense operator, or the live factors — are
    passed to the program as arguments; the rest is its compile key.
    """

    plan: ChainPlan
    fused: bool
    interpret: bool
    tune_source: str = field(default="default", compare=False)
    operands: tuple = field(default=(), compare=False)

    def apply(self, operands: Sequence, x):
        """The chain on ``x`` inside a trace (layout as above)."""
        b = x.shape[0]
        if self.fused:
            body = _fused_body(self.plan, b, _pad_to(b, self.plan.block_l),
                               self.interpret)
            return body(operands[0], x)
        from .ops import chain_nd   # lazy: ops imports stats, not fused
        y = chain_nd((None, None) + self.plan.fshapes, operands, x[None],
                     self.interpret)[0]
        for axis, op in enumerate(self.plan.epilogue):
            if op == "cumsum":
                y = jnp.cumsum(y, axis=axis + 1)
        return y

    def record(self, batch: int, t0: float) -> None:
        """Count one launch of this chain (stats.py) and its dispatch time."""
        _LAUNCHED.add((self.plan.signature, batch, self.fused))
        n_epi = sum(1 for op in self.plan.epilogue if op)
        if self.fused:
            for name in ("pads", "pallas_calls", "fused_chains", "slices"):
                CHAIN_STATS.inc(name)
        else:
            n_live = sum(1 for s in self.plan.fshapes if s is not None)
            for name in ("pads", "pallas_calls", "slices"):
                CHAIN_STATS.inc(name, n_live)
            CHAIN_STATS.inc("fallback_chains")
        CHAIN_STATS.inc("epilogue_axes", n_epi)
        label = chain_label(self.plan.in_dims, batch, self.plan.compute_dtype)
        _LAUNCH_SECONDS.labels(chain=label).observe(time.monotonic() - t0)

    def span(self, batch: int):
        """The ``kernel.chain`` span of one launch of this chain."""
        p = self.plan
        return TRACER.span("kernel.chain").set(
            chain=chain_label(p.in_dims, batch, p.compute_dtype),
            fused=self.fused, block_l=p.block_l,
            compute_dtype=p.compute_dtype, tune_source=self.tune_source,
            vmem_bytes=p.vmem_bytes)


def prepare_chain(factors: Sequence, dims: Sequence[int], batch: int,
                  interpret: Optional[bool] = None,
                  block_l: Optional[int] = None,
                  vmem_budget: Optional[int] = None,
                  epilogue: Optional[Sequence[Optional[str]]] = None,
                  compute_dtype: Optional[str] = None,
                  allow_narrow: bool = False) -> ChainLaunch:
    """Plan one chain of ``batch`` rows: path, launch config and operands.

    Launch-config resolution (docs/DESIGN.md §14): if any of ``block_l`` /
    ``vmem_budget`` / ``compute_dtype`` is passed explicitly, exactly those
    values are used (unset ones take the untuned defaults) and the autotuner
    is bypassed; an explicit ``vmem_budget`` also decides the path.
    Otherwise, when ``REPRO_KERNEL_AUTOTUNE`` is not ``off``, the tuned
    config for this chain signature is looked up (tuning it on the fly with
    the analytic cost model on a first miss).  ``allow_narrow`` gates the
    mixed-precision policy: chains that carry Gaussian noise lanes keep the
    default ``False`` so a tuned narrow compute dtype is clamped back to
    float32 — noise stays fp32, only the data path may narrow.
    """
    interpret = _interpret_default() if interpret is None else interpret
    dims = tuple(int(d) for d in dims)
    s_facs = [_normalize_factor(f, n) for f, n in zip(factors, dims)]
    explicit = (block_l is not None or vmem_budget is not None
                or compute_dtype is not None)
    source = "explicit" if explicit else "default"
    if not explicit:
        from repro.kernels.autotune import resolve_config
        cfg = resolve_config(s_facs, dims, batch=batch, epilogue=epilogue,
                             interpret=interpret)
        if cfg is not None:
            block_l = cfg.block_l
            compute_dtype = cfg.compute_dtype if allow_narrow else "float32"
            source = cfg.source
    compute_dtype = "float32" if compute_dtype is None else compute_dtype
    plan = plan_chain(s_facs, dims, batch=batch, block_l=block_l,
                      vmem_budget=vmem_budget, epilogue=epilogue,
                      compute_dtype=compute_dtype)
    # An explicit budget decides; otherwise the batch-independent footprint
    # rule does (the tuner only ever offers blocks that fit the chip).
    fused = plan.fused_ok if explicit and vmem_budget is not None else \
        chain_fuses(s_facs, dims, epilogue, plan.compute_dtype)
    live = [s for s in s_facs if s is not None]
    operands = (_dense_operator(plan, live),) if fused else \
        tuple(jnp.asarray(s, jnp.float32) for s in live)
    return ChainLaunch(plan, fused, interpret, source, operands)


@lru_cache(maxsize=None)
def _build_launch_call(launch: ChainLaunch):
    """A launch compiled alone (per-axis: N-D in and out)."""
    return jax.jit(launch.apply)


def fused_cache_info():
    return _build_launch_call.cache_info()


def apply_epilogue(y, out_dims: Sequence[int],
                   epilogue: Sequence[Optional[str]]) -> jnp.ndarray:
    """Implicit-W epilogue: cumsum along marked axes of a (B, Π out_dims) stack.

    Used by the batched jnp path; the fused kernel folds the same ops into
    its operator and the per-axis chain applies them on its N-D output
    (docs/DESIGN.md §8).  Pure — safe to jit; callers on the host bump
    ``CHAIN_STATS.epilogue_axes`` themselves so the counter reflects serving
    calls, not traces.
    """
    if not epilogue or all(op is None for op in epilogue):
        return y
    b = y.shape[0]
    t = jnp.asarray(y).reshape((b,) + tuple(out_dims))
    for axis, op in enumerate(epilogue):
        if op == "cumsum":
            t = jnp.cumsum(t, axis=axis + 1)
    return t.reshape(b, -1)


def fused_chain_matvec(factors: Sequence, x, dims: Sequence[int],
                       interpret: Optional[bool] = None,
                       block_l: Optional[int] = None,
                       vmem_budget: Optional[int] = None,
                       epilogue: Optional[Sequence[Optional[str]]] = None,
                       compute_dtype: Optional[str] = None,
                       allow_narrow: bool = False) -> jnp.ndarray:
    """Apply ``⊗_i factors[i]`` to a stack ``x`` of shape (B, N) (or flat (N,)).

    One pad, one pallas_call, one slice per chain (stats.py instruments the
    contract).  Chains whose footprint exceeds VMEM take the per-axis
    kernel; their stack crosses to N-D in host memory, where the reshape is
    free (ops.py).  ``epilogue`` marks axes for implicit-W ops
    (``'cumsum'``), see :func:`plan_chain`.  Returns shape (B, n_out) — or
    flat (n_out,) if the input was flat; the output dtype is always float32.
    Launch configs resolve as in :func:`prepare_chain`.
    """
    if not isinstance(x, jax.Array):
        x = np.asarray(x, np.float32)
    flat_in = x.ndim == 1
    if flat_in:
        x = x.reshape(1, -1)
    b = x.shape[0]
    dims = tuple(int(d) for d in dims)
    if x.shape[1] != math.prod(dims):
        raise ValueError(f"x width {x.shape[1]} != prod(dims) "
                         f"{math.prod(dims)}")
    s_facs = [_normalize_factor(f, n) for f, n in zip(factors, dims)]
    if all(s is None for s in s_facs):
        x = jnp.asarray(x, jnp.float32)
        if epilogue is not None and any(op for op in epilogue):
            x = apply_epilogue(x, dims, epilogue)
            CHAIN_STATS.inc("epilogue_axes", sum(1 for op in epilogue if op))
        return x[0] if flat_in else x
    launch = prepare_chain(s_facs, dims, b, interpret=interpret,
                           block_l=block_l, vmem_budget=vmem_budget,
                           epilogue=epilogue, compute_dtype=compute_dtype,
                           allow_narrow=allow_narrow)
    t0 = time.monotonic()
    with launch.span(b):
        call = _build_launch_call(launch)
        if launch.fused:
            y = call(launch.operands, x)
        else:
            x_nd = np.asarray(x, np.float32).reshape((b,) + dims)
            y = jnp.asarray(np.asarray(call(launch.operands, x_nd))
                            .reshape(b, -1))
    launch.record(b, t0)
    return y[0] if flat_in else y
