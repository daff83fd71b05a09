r"""Pallas TPU kernel for the paper's compute hot spot: Kronecker-factor matvec.

Every ResidualPlanner phase (measurement Alg 1/5, reconstruction Alg 2/6)
reduces to chains of  y = (I_L ⊗ S ⊗ I_R) x  applications — a *batched small
GEMM* with the small per-attribute matrix S (m, n).

TPU adaptation (docs/DESIGN.md §3.2): attribute sizes n are far below the
128×128 MXU tile, so the kernel gets its arithmetic intensity from the (L, R)
batch layout instead.  ops.py moves the contracted axis to the second-minor
position of the N-D tensor, so each application sees x as (L, n, R):

  * grid over (L/bl, R/br) blocks; R is the minor (lane) axis, br ∈ {128,
    256, 512} lanes chosen by ops.py to minimize padding;
  * S (m, n) is loaded into VMEM once and reused across the whole
    (bl × br) tile — m·n·bl·br MACs per (n·bl·br + m·bl·br) transfers,
    i.e. intensity ≈ m FLOP/byte vs O(1) for the naive gather formulation;
  * each of the bl leading rows is one 2-D ``S @ x[l]`` MXU contraction at
    ``Precision.HIGHEST``, so the body needs no transpose; m and n are
    zero-padded to multiples of 8 (sublane) by ops.py.

Validated in interpret mode on CPU against ref.py (the pure-jnp oracle used
by the rest of the library).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kron_axis_kernel(s_ref, x_ref, o_ref):
    """o[l] = S · x[l] for each leading row l of the (bl, n, br) block."""
    s = s_ref[...]
    for l in range(x_ref.shape[0]):
        o_ref[l] = jax.lax.dot_general(
            s, x_ref[l], dimension_numbers=(((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


def kron_axis_matvec(s: jnp.ndarray, x: jnp.ndarray, *, block_l: int = 8,
                     block_r: int = 512, interpret: bool = True) -> jnp.ndarray:
    """Apply S (m, n) along the middle axis of x (L, n, R) → (L, m, R).

    L and R must be multiples of block_l / block_r, and m, n of 8 (ops.py
    pads).
    """
    L, n, R = x.shape
    m = s.shape[0]
    assert s.shape[1] == n
    assert L % block_l == 0 and R % block_r == 0, (L, R, block_l, block_r)
    return pl.pallas_call(
        _kron_axis_kernel,
        grid=(L // block_l, R // block_r),
        in_specs=[
            pl.BlockSpec((m, n), lambda i, j: (0, 0)),
            pl.BlockSpec((block_l, n, block_r), lambda i, j: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((block_l, m, block_r), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((L, m, R), x.dtype),
        interpret=interpret,
        name="kron_axis",
    )(s, x)
