"""Implicit Kronecker-product linear algebra.

The whole paper rests on never materializing ``⊗_i V_i``.  A Kronecker matvec
``(V_1 ⊗ … ⊗ V_k) x`` is evaluated by reshaping ``x`` to the tensor
``(n_1, …, n_k)`` and contracting each factor along its own axis — the fast
kron-vector multiplication of McKenna et al. [40] referenced by Algs 1/2/5/6.

Two implementations are provided:
  * ``kron_matvec``      — jax/jnp, jit- and vmap-friendly (device path);
  * ``kron_matvec_np``   — numpy (planning / host path, exact float64).

``None`` factors mean "identity on that axis" and are skipped.
A factor may also be the string ``"ones"`` meaning the all-ones row vector
(marginalize the axis out) — the most common non-identity factor in the paper.
"""
from __future__ import annotations

from functools import reduce
from typing import List, Optional, Sequence, Union

import numpy as np

import jax
import jax.numpy as jnp

Factor = Union[None, str, np.ndarray, "jnp.ndarray"]


def _apply_axis_jnp(x, mat, axis: int):
    # HIGHEST: on a TPU the default float32 contraction is a reduced-precision
    # MXU pass, too coarse for counts in the hundreds of thousands.
    x = jnp.moveaxis(x, axis, 0)
    y = jnp.tensordot(mat, x, axes=([1], [0]),
                      precision=jax.lax.Precision.HIGHEST)
    return jnp.moveaxis(y, 0, axis)


def kron_matvec(factors: Sequence[Factor], x, dims: Sequence[int]):
    """Apply ``⊗_i factors[i]`` to ``x`` (any leading layout, flattened ok) with jnp.

    dims: the per-axis input sizes n_i (needed to reshape a flat x).
    Returns the result flattened to 1-D.
    """
    x = jnp.asarray(x).reshape(tuple(dims))
    for axis, f in enumerate(factors):
        if f is None:
            continue
        if isinstance(f, str):
            if f == "ones":
                x = jnp.sum(x, axis=axis, keepdims=True)
                continue
            raise ValueError(f)
        x = _apply_axis_jnp(x, jnp.asarray(f), axis)
    return x.reshape(-1)


def kron_matvec_batched(factors: Sequence[Factor], x, dims: Sequence[int]):
    """Apply ``⊗_i factors[i]`` to every row of a stack ``x`` (B, Π dims) with jnp.

    The batch axis is the same "left" dimension the Pallas kernels tile; this
    is the device-side analogue of the signature-batched numpy path
    (docs/DESIGN.md §4).  Returns shape (B, Π out_dims).
    """
    x = jnp.asarray(x)
    b = x.shape[0]
    x = x.reshape((b,) + tuple(dims))
    for axis, f in enumerate(factors):
        if f is None:
            continue
        if isinstance(f, str):
            if f == "ones":
                x = jnp.sum(x, axis=axis + 1, keepdims=True)
                continue
            raise ValueError(f)
        x = _apply_axis_jnp(x, jnp.asarray(f), axis + 1)
    return x.reshape(b, -1)


def kron_matvec_np(factors: Sequence[Factor], x: np.ndarray,
                   dims: Sequence[int]) -> np.ndarray:
    x = np.asarray(x, dtype=np.float64).reshape(tuple(dims))
    for axis, f in enumerate(factors):
        if f is None:
            continue
        if isinstance(f, str):
            if f == "ones":
                x = np.sum(x, axis=axis, keepdims=True)
                continue
            raise ValueError(f)
        f = np.asarray(f, dtype=np.float64)
        x = np.moveaxis(np.tensordot(f, np.moveaxis(x, axis, 0), axes=([1], [0])), 0, axis)
    return x.reshape(-1)


def kron_matvec_np_batched(factors: Sequence[np.ndarray], x: np.ndarray,
                           dims: Sequence[int]) -> np.ndarray:
    """Batched host Kron chain: apply ``⊗_i factors[i]`` to every row of
    ``x`` (B, Π dims) with numpy tensordots.

    Deliberately dtype-preserving — the secure path routes int64 and object
    (big-int) lanes through it; float callers cast their inputs first.
    """
    b = x.shape[0]
    x = x.reshape((b,) + tuple(dims))
    for axis, f in enumerate(factors):
        x = np.moveaxis(np.tensordot(f, np.moveaxis(x, axis + 1, 0),
                                     axes=([1], [0])), 0, axis + 1)
    return x.reshape(b, -1)


def kron_expand(factors: Sequence[np.ndarray]) -> np.ndarray:
    """Materialize a small Kronecker product (tests / tiny domains only)."""
    mats = [np.asarray(f, dtype=np.float64) for f in factors]
    return reduce(np.kron, mats) if mats else np.ones((1, 1))


def kron_out_dims(factors: Sequence[Factor], dims: Sequence[int]) -> List[int]:
    out = []
    for f, n in zip(factors, dims):
        if f is None:
            out.append(n)
        elif isinstance(f, str):
            out.append(1)
        else:
            out.append(int(np.asarray(f).shape[0]))
    return out
