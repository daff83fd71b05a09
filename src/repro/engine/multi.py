"""Cross-request signature-batched measurement (the serving tier's fuse point).

``measure`` (core/mechanism.py) batches the cliques of ONE plan by per-axis
signature; this module generalizes the same trick across *requests*: the
``[v; z]`` pairs of every (request, clique) whose signature matches — even
when the requests come from different tenants with different plans and
different budgets — stack into the batch axis of a single fused chain launch.
Eight tenants asking for the same ≤2-way workload shape cost the same number
of kernel launches as one tenant (docs/DESIGN.md §13).

Bit-exactness contract: each request's noise is drawn from its own key with
the exact fold order of the per-request path (``jax.random.split(key,
len(plan.cliques))`` indexed by clique position), and vmapped threefry draws
match per-key draws exactly — so ``measure_multi(items)`` returns
measurement-for-measurement the same bits as calling ``measure(plan, margs,
key)`` once per item.  The cross-tenant batching test and the serve benchmark
both assert this.

Only plain-marginal plans qualify (their chain is determined by the
attribute-size signature alone); RP+/composite/secure plans are served
per-request through their cached engines by the caller.
"""
from __future__ import annotations

import math
import time
from collections import defaultdict
from dataclasses import dataclass, replace
from functools import lru_cache
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.domain import Clique
from repro.core.kron import kron_matvec_batched
from repro.core.mechanism import Measurement, noise_dtype
from repro.core.residual import sub_matrix
from repro.core.select import Plan
from repro.kernels.kron_matvec._layout import resolve_use_kernel
from repro.kernels.kron_matvec.fused import ChainLaunch, prepare_chain
from repro.obs import TRACER

MultiItem = Tuple[Plan, Mapping[Clique, jnp.ndarray], jax.Array]


def can_fuse(plan) -> bool:
    """True iff this plan's measurement chains are cross-request fusable.

    Plain :class:`~repro.core.select.Plan` chains are fully determined by the
    attribute-size signature, so two requests with equal signatures share one
    chain.  RP+ plans carry per-attribute (Sub, Γ) factors and composite
    plans fan out to block engines — both are served per-request.
    """
    return type(plan) is Plan


def lane_bucket(g: int) -> int:
    """Lane-count bucket of a signature group: the next power of two, at
    least 8, so chain shapes repeat across drains of different sizes (pad
    lanes are zero marginals with a recycled key, sliced away after)."""
    g_pad = 8
    while g_pad < g:
        g_pad *= 2
    return g_pad


# Smallest noise row a lane draws; see _noise_row.
_MIN_NOISE_ROW = 128


def _noise_row(m: int) -> int:
    """Width of the noise row a lane of m cells draws: a power of two.

    With partitionable threefry (JAX's default) element i of
    ``normal(key, (M,))`` depends only on the key and i, so the first m
    elements of a wider draw ARE ``normal(key, (m,))``.  Drawing every lane
    at one of a few power-of-two widths lets all lanes of a batch share a
    handful of compiled draws instead of one per signature: a TPU compiles
    a large random draw in seconds.
    """
    row = _MIN_NOISE_ROW
    while row < m:
        row *= 2
    return row


@dataclass(frozen=True)
class _GroupSpec:
    """The static part of one signature group of a measurement program."""

    dims: Tuple[int, ...]
    g: int                          # real lanes
    g_pad: int                      # bucketed lanes
    launch: Optional[ChainLaunch]   # None: batched jnp (or no chain)
    row: int = 0                    # first lane in its noise-width bucket

    @property
    def m(self) -> int:
        return math.prod(self.dims) if self.dims else 1

    @property
    def nd(self) -> bool:
        """Per-axis chains take the stack N-D (kernels/kron_matvec/ops.py)."""
        return self.launch is not None and not self.launch.fused


def _draw_noise(widths: Tuple[int, ...], dtype, keys):
    """Every lane's N(0, I) row, one compiled draw per row width."""
    return tuple(jax.lax.map(
        lambda k, w=w: jax.random.normal(k, (w,), dtype=dtype), ks)
        for w, ks in zip(widths, keys))


def _combine(spec: _GroupSpec, x, sig):
    g, zr = spec.g, spec.g_pad
    return x[:g] + sig.reshape((g,) + (1,) * (x.ndim - 1)) * x[zr:zr + g]


@lru_cache(maxsize=64)
def _flat_program(specs: Tuple[_GroupSpec, ...], widths: Tuple[int, ...],
                  dtype):
    """Program 1 of a batch: every lane's noise, and ω = H v + σ H z for
    every group whose chain takes the stack flat; the per-axis groups get
    their noise rows back, to be laid out N-D on the host.

    One program per batch layout instead of one per group: a release plan
    has hundreds of signature groups, and each program costs compile time.
    """
    def run(keys, args):
        noise = dict(zip(widths, _draw_noise(widths, dtype, keys)))
        outs = []
        for spec, (v, sig, operands) in zip(specs, args):
            z = noise[_noise_row(spec.m)][spec.row:spec.row + spec.g_pad,
                                          :spec.m]
            if spec.nd:
                outs.append(z)
                continue
            x = jnp.concatenate([v.astype(dtype), z], axis=0)
            if spec.dims:
                x = (spec.launch.apply(operands, x) if spec.launch is not None
                     else kron_matvec_batched(operands, x, spec.dims))
            outs.append(_combine(spec, x, sig))
        return tuple(outs)

    return jax.jit(run)


@lru_cache(maxsize=64)
def _nd_program(specs: Tuple[_GroupSpec, ...], dtype):
    """Program 2 of a batch: the per-axis groups on N-D stacks."""
    def run(args):
        return tuple(_combine(spec, spec.launch.apply(
            operands, jnp.concatenate([v.astype(dtype), z.astype(dtype)],
                                      axis=0)), sig)
            for spec, (v, z, sig, operands) in zip(specs, args))

    return jax.jit(run)


def measure_multi(items: Sequence[MultiItem],
                  use_kernel: Optional[bool] = None,
                  dtype=None) -> List[Dict[Clique, Measurement]]:
    """Algorithm 1 for many requests at once: one chain launch per signature.

    ``items[i] = (plan, marginals, key)`` exactly as the per-request
    ``measure(plan, marginals, key)`` would receive them; the return value is
    the list of per-request measurement dicts, bit-identical to the
    per-request path.  Requests are grouped by attribute-size signature
    ACROSS items, so the launch count is the number of distinct signatures in
    the union — not the sum of per-request signature counts — and the whole
    batch runs as one compiled program.  ``use_kernel=None`` resolves from
    the backend (Pallas on a TPU).
    """
    for plan, _m, _k in items:
        if not can_fuse(plan):
            raise ValueError(
                f"measure_multi serves plain marginal plans only, got "
                f"{type(plan).__name__}; route this request through "
                f"plan.engine().measure")
    return measure_batch(items, use_kernel, dtype)


def measure_batch(items: Sequence[MultiItem], use_kernel: Optional[bool],
                  dtype) -> List[Dict[Clique, Measurement]]:
    """The measurement of :func:`measure_multi`, for any plain-table plans
    (``core.mechanism.measure`` serves one request through it)."""
    use_kernel = resolve_use_kernel(use_kernel)
    dtype = jnp.dtype(noise_dtype() if dtype is None else dtype)
    # (signature dims) -> list of (item_idx, clique, per-clique key row).
    # Keys are pulled host-side once per item; per-lane jax-array indexing
    # would pay one dispatch per lane.
    groups: Dict[tuple, List[tuple]] = defaultdict(list)
    for i, (plan, _margs, key) in enumerate(items):
        keys = np.asarray(jax.random.split(key, len(plan.cliques)))
        for pos, c in enumerate(plan.cliques):
            dims = tuple(plan.domain.attributes[a].size for a in c)
            groups[dims].append((i, c, keys[pos]))

    specs, args, lane_keys, sig2s = [], [], [], []
    for dims, members in groups.items():
        with TRACER.span("measure.multi.group").set(
                dims="x".join(map(str, dims)) if dims else "scalar",
                lanes=len(members)):
            spec, arg, keys_np, s2 = _prepare_group(items, dims, members,
                                                    use_kernel, dtype)
        specs.append(spec)
        args.append(arg)
        lane_keys.append(keys_np)
        sig2s.append(s2)
    # Lanes of one noise width are drawn together: give each group its
    # first row in its width's key stack.
    widths = tuple(sorted({_noise_row(spec.m) for spec in specs}))
    rows = dict.fromkeys(widths, 0)
    stacks: Dict[int, list] = {w: [] for w in widths}
    for i, (spec, keys_np) in enumerate(zip(specs, lane_keys)):
        w = _noise_row(spec.m)
        specs[i] = replace(spec, row=rows[w])
        rows[w] += spec.g_pad
        stacks[w].append(keys_np)
    keys = tuple(np.concatenate(stacks[w]) for w in widths)

    t0 = time.monotonic()
    with TRACER.span("measure.multi.launch").set(groups=len(specs)):
        for spec in specs:      # one kernel.chain marker per chain launched
            if spec.launch is not None:
                with spec.launch.span(2 * spec.g_pad):
                    pass
        outs = [np.asarray(o) for o in _flat_program(
            tuple(specs), widths, dtype)(keys, tuple(args))]
        nd = [i for i, spec in enumerate(specs) if spec.nd]
        if nd:
            # The per-axis chains take N-D stacks: the reshape is free in
            # host memory and costs seconds of compile per shape on a TPU.
            nd_args = []
            for i in nd:
                v, sig, operands = args[i]
                shape = (specs[i].g_pad,) + specs[i].dims
                nd_args.append((np.asarray(v).reshape(shape),
                                outs[i].reshape(shape), sig, operands))
            nd_outs = _nd_program(tuple(specs[i] for i in nd), dtype)(
                tuple(nd_args))
            for i, o in zip(nd, nd_outs):
                outs[i] = np.asarray(o)
    for spec in specs:
        if spec.launch is not None:
            spec.launch.record(2 * spec.g_pad, t0)

    out: List[Dict[Clique, Measurement]] = [dict() for _ in items]
    for spec, members, om, s2 in zip(specs, groups.values(), outs, sig2s):
        om = om.reshape(spec.g, -1)
        for j, (i, c, _k) in enumerate(members):
            out[i][c] = Measurement(c, om[j], s2[j])
    return out


def _prepare_group(items, dims, members, use_kernel, dtype):
    """One signature group's lanes on the host, and its chain planned.

    Returns ``(spec, args, keys, sig2s)`` — the group's static spec, its
    program arguments ``(v, σ, operands)``, its lane keys and the per-lane
    σ² in member order.
    """
    m = math.prod(dims) if dims else 1
    # Lane assembly happens HOST-SIDE in one numpy stack + ONE device
    # transfer per group: a per-lane jnp.asarray/jnp.stack loop costs
    # ~0.5 ms of eager dispatch per lane, which at hundreds of lanes per
    # batch would swamp the launch savings the fusion exists to deliver.
    vs, sig2s = [], []
    for i, c, _k in members:
        v = np.asarray(items[i][1][c]).reshape(-1)
        if v.shape[0] != m:
            raise ValueError(
                f"marginal for {c} (request {i}) has {v.shape[0]} cells, "
                f"want {m}")
        vs.append(v)
        sig2s.append(items[i][0].sigmas[c])
    # Row-independence of the batched contraction keeps the real lanes
    # bit-identical to an unpadded launch (test-enforced).
    g = len(members)
    g_pad = lane_bucket(g)
    vnp = np.zeros((g_pad, m), np.dtype(dtype.name))
    vnp[:g] = np.stack(vs)
    keys_np = np.stack([k for _i, _c, k in members]
                       + [members[0][2]] * (g_pad - g))
    launch, operands = None, ()
    if dims:
        factors = [sub_matrix(n) for n in dims]
        if use_kernel:
            launch = prepare_chain(factors, dims, 2 * g_pad)
            operands = launch.operands
        else:
            operands = tuple(jnp.asarray(f, dtype) for f in factors)
    sig = np.sqrt(np.asarray(sig2s)).astype(dtype.name)
    return (_GroupSpec(dims, g, g_pad, launch), (vnp, sig, operands),
            keys_np, sig2s)
