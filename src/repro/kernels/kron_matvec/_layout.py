"""Layout helpers shared by the per-axis (ops.py) and fused (fused.py) paths.

One definition of padding, backend detection and factor normalization keeps
the two kernel paths in exact agreement about what a factor *means* — an
identity matrix, ``None`` and a skipped axis must be the same thing on both.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

import jax


def interpret_default() -> bool:
    """Interpret-mode Pallas everywhere except a real TPU backend."""
    return jax.default_backend() != "tpu"


def resolve_use_kernel(use_kernel: Optional[bool]) -> bool:
    """``None`` → the backend's own chain path: the Pallas kernels on a TPU,
    batched jnp elsewhere (interpret-mode Pallas only adds Python
    overhead there).  Every serving entry point resolves it here."""
    return (not interpret_default()) if use_kernel is None else bool(use_kernel)


def pad_to(x: int, m: int) -> int:
    return -(-x // m) * m


def normalize_factor(f, n: int) -> Optional[np.ndarray]:
    """None/identity → None (axis untouched); 'ones' → (1, n) row; else matrix."""
    if f is None:
        return None
    if isinstance(f, str):
        if f == "ones":
            return np.ones((1, n), dtype=np.float32)
        raise ValueError(f)
    f = np.asarray(f, dtype=np.float32)
    if f.shape == (n, n) and np.allclose(f, np.eye(n)):
        return None   # explicit identity: skip the contraction
    return f
