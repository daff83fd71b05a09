"""jit'd wrappers around the per-axis Pallas kron kernel.

``kron_matvec_kernel`` applies a full chain ⊗_i S_i by invoking the per-axis
kernel once per non-trivial factor on the N-D tensor ``(n_1, …, n_k)``
(docs/DESIGN.md §3.2).  For each factor the contracted axis is moved to the
second-minor position (the minor axis itself trades places with its
neighbour), the leading axes merge into L, and the kernel sees
``(L, n, R)`` with R on the lanes; (m, n) pad to sublane multiples of 8 and
R to the lane block, then slice back.  Only transposes of whole axes and
merges of leading axes run on the device: on a TPU those compile in well
under a second, whereas a reshape that changes the minor (lane) dimension
of a large array costs seconds of compile per shape.  The flat input is
therefore reshaped to N-D in host memory (free there) before it reaches the
device, and the N-D result is flattened the same way.  The whole chain is
one jitted program per signature.

``residual_measure_kernel`` fuses the measurement Hv + σHz by stacking
[v, z] as a leading identity axis so both transforms share every S tile —
the Alg 1/Alg 5 hot path in one sweep.

This is the *fallback and oracle* path: it pays one pad → HBM round-trip →
slice per factor.  The production chain path is fused.py, which runs the
whole chain as one kernel (docs/DESIGN.md §3.3–3.4).

interpret=True (automatic on CPU) runs the kernel body in Python for
correctness validation; on TPU backends the real Mosaic lowering is used.
"""
from __future__ import annotations

import math
from functools import lru_cache
from typing import Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ._layout import interpret_default as _interpret_default
from ._layout import normalize_factor as _normalize_factor
from ._layout import pad_to as _pad_to
from .kron_matvec import kron_axis_matvec
from .stats import CHAIN_STATS

_LANE_BLOCKS = (512, 256, 128)
_SUB = 8


def _lane_block(cols: int) -> int:
    """Widest lane block among 512/256/128 with the least padding of cols."""
    return min(_LANE_BLOCKS, key=lambda br: (_pad_to(cols, br), -br))


def _apply_axis(s: jnp.ndarray, x: jnp.ndarray, axis: int,
                interpret: bool) -> jnp.ndarray:
    """Contract ``axis`` of the N-D tensor x with S (m, n) via the kernel."""
    nd = x.ndim
    pos = nd - 2
    if axis == nd - 1:               # the minor axis trades with its neighbour
        x = jnp.swapaxes(x, nd - 1, nd - 2)
    else:
        x = jnp.moveaxis(x, axis, pos)
    lead = x.shape[:-2]
    n, R = x.shape[-2:]
    m = s.shape[0]
    L = math.prod(lead)
    n_p, m_p = _pad_to(n, _SUB), _pad_to(m, _SUB)
    block_l = min(_SUB, L)
    block_r = _lane_block(R)
    L_p, R_p = _pad_to(L, block_l), _pad_to(R, block_r)
    s_p = jnp.pad(s.astype(x.dtype), ((0, m_p - m), (0, n_p - n)))
    x_p = jnp.pad(x.reshape(L, n, R),
                  ((0, L_p - L), (0, n_p - n), (0, R_p - R)))
    y = kron_axis_matvec(s_p, x_p, block_l=block_l, block_r=block_r,
                         interpret=interpret)
    y = y[:L, :m, :R].reshape(lead + (m, R))
    if axis == nd - 1:
        return jnp.swapaxes(y, nd - 1, nd - 2)
    return jnp.moveaxis(y, pos, axis)


def chain_nd(fshapes: Tuple[Optional[Tuple[int, int]], ...],
             live: Sequence, x: jnp.ndarray, interpret: bool) -> jnp.ndarray:
    """The chain on an N-D tensor inside a trace: ``fshapes[i]`` is axis
    i's factor shape (None: identity), ``live`` the non-identity factors."""
    facs = iter(live)
    for axis, spec in enumerate(fshapes):
        if spec is not None:
            x = _apply_axis(jnp.asarray(next(facs)), x, axis, interpret)
    return x


@lru_cache(maxsize=None)
def _build_chain_call(fshapes: Tuple[Optional[Tuple[int, int]], ...],
                      interpret: bool):
    """One jitted program per chain signature, N-D in and N-D out."""
    def call(*args):
        *live, x = args
        return chain_nd(fshapes, live, x, interpret)

    return jax.jit(call)


def kron_matvec_kernel(factors: Sequence, x, dims: Sequence[int],
                       interpret: Optional[bool] = None) -> np.ndarray:
    """(⊗_i factors[i]) x with the Pallas per-axis kernel (flat in and out).

    The reshapes between the flat vector and the ``(1, n_1, …, n_k)``
    tensor happen in host memory; the leading unit axis gives a one-axis
    chain a neighbour to trade its minor position with.
    """
    interpret = _interpret_default() if interpret is None else interpret
    dims = (1,) + tuple(int(d) for d in dims)
    x_nd = np.asarray(x, np.float32).reshape(dims)
    s_facs = [None] + [_normalize_factor(f, n)
                       for f, n in zip(factors, dims[1:])]
    live = [s for s in s_facs if s is not None]
    if not live:
        return x_nd.reshape(-1)
    fshapes = tuple(None if s is None else tuple(s.shape) for s in s_facs)
    y = _build_chain_call(fshapes, interpret)(*live, x_nd)
    for name in ("pads", "pallas_calls", "slices"):
        CHAIN_STATS.inc(name, len(live))
    return np.asarray(y).reshape(-1)


def residual_measure_kernel(factors: Sequence, v: jnp.ndarray, z: jnp.ndarray,
                            sigma: float, dims: Sequence[int],
                            interpret: Optional[bool] = None) -> jnp.ndarray:
    """Fused measurement  H v + σ H z  (Algorithm 1 / 5 hot path).

    [v; z] ride a leading identity axis of the same kernel invocations, so
    every S-tile load is shared between the data pass and the noise pass.
    """
    stacked = np.stack([np.asarray(v, np.float32).reshape(-1),
                        np.asarray(z, np.float32).reshape(-1)])
    out = kron_matvec_kernel([None] + list(factors), stacked,
                             (2,) + tuple(int(d) for d in dims),
                             interpret=interpret).reshape(2, -1)
    return out[0] + sigma * out[1]
