"""Reusable analytic cost model shared by the roofline reports and the
kernel autotuner (docs/DESIGN.md §14).

The dormant dry-run analyzer (analyze.py) hard-coded TPU-v5e constants and
only consumed offline HLO artifacts.  This module factors the hardware
knowledge into a small device table + runtime detection, and adds a *chain*
cost model: predicted FLOPs / HBM bytes / arithmetic intensity / wall time
for one fused Kron-chain launch under a candidate ``(block_l, vmem_budget,
compute_dtype, fused-vs-per-axis)`` config.  The tuner
(``repro.kernels.autotune``) ranks candidate configs with it; the roofline
report (analyze.py) reuses the same roofline terms for dry-run artifacts.

Two regimes matter:

* **real accelerator** — per-step launch overhead is negligible; the model is
  the classic roofline ``max(flops/peak, bytes/bw)`` with the VMEM ceiling as
  a hard feasibility constraint on the fused working tile;
* **interpret mode (CPU CI)** — the Pallas kernel body is executed by a
  Python interpreter once per grid step, so per-step overhead dominates and
  the model's job is to minimize grid steps subject to padding waste.  The
  "VMEM" limit is a host-cache working-set bound, not a hardware register
  file, so it is far looser than on TPU.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

_MIB = 1024 * 1024


@dataclass(frozen=True)
class DeviceSpec:
    """Per-device-kind constants the cost model and tuner consume.

    ``peak_flops`` is the narrow-dtype (bf16) MXU peak; ``peak_flops_f32``
    the fp32 peak.  ``vmem_limit`` is the hard ceiling a fused working tile
    may occupy; ``default_vmem_budget`` is the conservative *untuned* budget
    (the CPU/interpret row uses the v5e default so interpret-mode plans
    match the chip's).  ``step_overhead_s`` is the per-grid-step launch cost —
    microseconds on real hardware, milliseconds for the Python interpreter.
    """

    kind: str
    peak_flops: float            # narrow (bf16) FLOP/s per chip
    peak_flops_f32: float        # fp32 FLOP/s per chip
    hbm_bw: float                # HBM bytes/s per chip
    ici_bw: float                # bytes/s per ICI link
    vmem_limit: int              # hard ceiling for a fused working tile
    default_vmem_budget: int     # untuned plan_chain budget
    step_overhead_s: float       # per grid-step launch overhead
    interpret: bool = False      # Pallas interpret mode (kernel body in Python)
    source: str = ""             # where the peaks come from

    def peak_for(self, compute_dtype: str) -> float:
        return self.peak_flops_f32 if compute_dtype == "float32" \
            else self.peak_flops


# Known device kinds (``jax.devices()[0].device_kind``, lower-cased).  The
# bf16 peak and HBM bandwidth of each TPU row are the published per-chip
# figures named in ``source``; no fp32 MXU peak is published, so
# ``peak_flops_f32`` is an assumed half of the bf16 peak.  ``ici_bw`` and
# ``step_overhead_s`` are assumptions too.  TPU VMEM budgets leave headroom
# under the 16 MiB default scoped limit (Pallas TPU docs).  A kind that is not
# here is an error (:func:`device_spec`): specs are never guessed.
DEVICE_TABLE = {
    "cpu": DeviceSpec("cpu", peak_flops=2e11, peak_flops_f32=1e11,
                      hbm_bw=5e10, ici_bw=1e10,
                      vmem_limit=256 * _MIB, default_vmem_budget=8 * _MIB,
                      step_overhead_s=2e-3, interpret=True,
                      source="assumed host figures (interpret mode)"),
    "tpu v4": DeviceSpec("tpu v4", peak_flops=275e12, peak_flops_f32=137e12,
                         hbm_bw=1228e9, ici_bw=50e9,
                         vmem_limit=16 * _MIB, default_vmem_budget=8 * _MIB,
                         step_overhead_s=2e-6,
                         source="Google Cloud, 'TPU v4': 275 TFLOP/s bf16, "
                                "1228 GB/s, 32 GiB HBM"),
    "tpu v5 lite": DeviceSpec("tpu v5 lite", peak_flops=197e12,
                              peak_flops_f32=98e12,
                              hbm_bw=819e9, ici_bw=50e9,
                              vmem_limit=16 * _MIB,
                              default_vmem_budget=8 * _MIB,
                              step_overhead_s=2e-6,
                              source="Google Cloud, 'TPU v5e': 197 TFLOP/s "
                                     "bf16, 819 GB/s, 16 GB HBM"),
    "tpu v5p": DeviceSpec("tpu v5p", peak_flops=459e12, peak_flops_f32=229e12,
                          hbm_bw=2765e9, ici_bw=100e9,
                          vmem_limit=16 * _MIB, default_vmem_budget=8 * _MIB,
                          step_overhead_s=2e-6,
                          source="Google Cloud, 'TPU v5p': 459 TFLOP/s bf16, "
                                 "2765 GB/s, 95 GB HBM"),
    "tpu v6 lite": DeviceSpec("tpu v6 lite", peak_flops=918e12,
                              peak_flops_f32=459e12,
                              hbm_bw=1640e9, ici_bw=100e9,
                              vmem_limit=32 * _MIB,
                              default_vmem_budget=16 * _MIB,
                              step_overhead_s=2e-6,
                              source="Google Cloud, 'TPU v6e': 918 TFLOP/s "
                                     "bf16, 1640 GB/s, 32 GB HBM"),
}

_ALIASES = {"tpu v5e": "tpu v5 lite", "tpu v5litepod": "tpu v5 lite",
            "tpu v5": "tpu v5p", "tpu v6e": "tpu v6 lite"}


def device_spec(kind: str) -> DeviceSpec:
    """The :class:`DeviceSpec` of a ``device_kind`` string.

    Raises ``KeyError`` for a kind absent from :data:`DEVICE_TABLE`: the
    cost model, the tuner and the VMEM budgets must never run on another
    device's numbers.
    """
    k = kind.strip().lower()
    k = _ALIASES.get(k, k)
    if k not in DEVICE_TABLE:
        raise KeyError(f"device kind {kind!r} is not in DEVICE_TABLE "
                       f"(known: {sorted(DEVICE_TABLE)}); add a row with the "
                       f"source of its peaks")
    return DEVICE_TABLE[k]


_DETECTED: Optional[DeviceSpec] = None


def detect_device(refresh: bool = False) -> DeviceSpec:
    """DeviceSpec of the runtime's default jax device (cached per process).

    Errors propagate: a backend that cannot list its devices, or a device
    kind the table does not know, raises instead of planning on CPU specs.
    """
    global _DETECTED
    if _DETECTED is None or refresh:
        import jax
        _DETECTED = device_spec(jax.devices()[0].device_kind)
    return _DETECTED


@dataclass(frozen=True)
class ChainCost:
    """Predicted cost of ONE fused Kron-chain launch under a config."""

    flops: float                 # MXU FLOPs over the padded batch
    hbm_bytes: float             # pad-in + factor loads + slice-out traffic
    intensity: float             # flops / hbm_bytes
    grid_steps: int
    tile_bytes: int              # fused working tile (ChainPlan.vmem_bytes)
    fits: bool                   # tile_bytes <= device vmem_limit
    t_compute: float
    t_memory: float
    t_overhead: float
    predicted_s: float           # max(compute, memory) + overhead

    def as_dict(self) -> dict:
        return {"flops": self.flops, "hbm_bytes": self.hbm_bytes,
                "intensity": round(self.intensity, 3),
                "grid_steps": self.grid_steps,
                "tile_bytes": self.tile_bytes, "fits": self.fits,
                "predicted_s": self.predicted_s}


def _pad_to(x: int, m: int) -> int:
    return -(-x // m) * m


class CostModel:
    """Analytic roofline scorer for chain launch configs and HLO artifacts.

    One instance per :class:`DeviceSpec`; stateless beyond the spec, so a
    module-level instance per device is safe to share between the tuner and
    the report paths.
    """

    def __init__(self, device: Optional[DeviceSpec] = None):
        self.device = detect_device() if device is None else device

    # ------------------------------------------------------------ fused chain
    def chain_cost(self, plan, batch: int) -> ChainCost:
        """Cost of one fused launch of ``plan`` (a ChainPlan) at ``batch``.

        The fused kernel is a tiled matmul of the padded ``(B_p, W_in)``
        stack with the dense ``(W_in, W_out)`` chain operator, so its MXU
        work is ``2 · W_in · W_out`` FLOPs per padded row over a grid of
        (row, output-column, contraction) blocks.  HBM traffic: the zero-pad
        write, the operator build, one read of each input block per output-
        column block and of each operator block per row block, and the
        output write + slice-back.  All widths are the *padded* widths:
        padding waste is a real cost the tuner must see, which is what stops
        it from rounding a 2280-row batch up to a 4096-row power of two.
        """
        dev = self.device
        isz = _itemsize(plan.compute_dtype)
        b_p = _pad_to(max(batch, 1), plan.block_l)
        row_blocks = b_p // plan.block_l
        col_blocks = plan.w_out // plan.block_n
        steps = row_blocks * col_blocks * (plan.w_in // plan.block_k)
        op_bytes = plan.w_in * plan.w_out * isz
        # A whole-operator block keeps its index across the grid and is read
        # once; lane-tiled operator blocks are read again per row block.
        whole = plan.block_k == plan.w_in and plan.block_n == plan.w_out
        operator_bytes = op_bytes * (2.0 if whole else 1.0 + row_blocks)
        in_bytes = b_p * plan.w_in * isz * (1.0 + col_blocks)  # pad + reads
        out_bytes = 2.0 * b_p * plan.w_out * 4          # write + slice (fp32)
        hbm = in_bytes + out_bytes + operator_bytes
        flops = 2.0 * plan.w_in * plan.w_out * b_p
        t_c = flops / dev.peak_for(plan.compute_dtype)
        t_m = hbm / dev.hbm_bw
        t_o = steps * dev.step_overhead_s
        return ChainCost(flops=flops, hbm_bytes=hbm,
                         intensity=flops / hbm if hbm else 0.0,
                         grid_steps=steps, tile_bytes=plan.vmem_bytes,
                         fits=plan.vmem_bytes <= dev.vmem_limit,
                         t_compute=t_c, t_memory=t_m, t_overhead=t_o,
                         predicted_s=max(t_c, t_m) + t_o)

    def per_axis_cost(self, in_dims: Sequence[int],
                      fshapes: Sequence[Optional[Tuple[int, int]]],
                      batch: int) -> float:
        """Predicted seconds for the per-axis fallback path: one pad → HBM
        round-trip → slice per non-trivial factor.  Each axis runs on the
        ``(lead, n, cols)`` layout of ops.py — ``(L, n, R)``, or
        ``(1, n, L·R)`` after a rotation when ``R < 128`` (which adds a
        transpose each way) — with up-to-8-row × 512-lane blocks."""
        dev = self.device
        cur = list(in_dims)
        total = 0.0
        for axis, spec in enumerate(fshapes):
            if spec is None:
                continue
            m, n = spec
            left = max(batch, 1) * (math.prod(cur[:axis]) if axis else 1)
            right = math.prod(cur[axis + 1:]) if axis + 1 < len(cur) else 1
            rotate = right < 128
            lead, cols = (1, left * right) if rotate else (left, right)
            bl = min(8, lead)
            l_p, c_p = _pad_to(lead, bl), _pad_to(cols, 128)
            n_p, m_p = _pad_to(n, 8), _pad_to(m, 8)
            in_b = 2.0 * l_p * n_p * c_p * 4
            out_b = 2.0 * l_p * m_p * c_p * 4
            if rotate:                       # transpose in and back out
                in_b += 2.0 * left * n * right * 4
                out_b += 2.0 * left * m * right * 4
            flops = 2.0 * m_p * n_p * l_p * c_p
            steps = (l_p // bl) * -(-c_p // 512)
            total += max(flops / dev.peak_flops_f32,
                         (in_b + out_b) / dev.hbm_bw) \
                + steps * dev.step_overhead_s
            cur[axis] = m
        return total

    # -------------------------------------------------------- roofline terms
    def roofline_terms(self, flops: float, hbm_bytes: float,
                       coll_bytes: float = 0.0, chips: int = 1) -> dict:
        """The three classic terms for a global (all-chip) workload."""
        dev = self.device
        t_compute = flops / (chips * dev.peak_flops)
        t_memory = hbm_bytes / (chips * dev.hbm_bw)
        t_collective = coll_bytes / (chips * dev.ici_bw)
        terms = {"compute": t_compute, "memory": t_memory,
                 "collective": t_collective}
        bottleneck = max(terms, key=terms.get)
        return {"t_compute": t_compute, "t_memory": t_memory,
                "t_collective": t_collective, "bottleneck": bottleneck,
                "t_dominant": terms[bottleneck]}


def _itemsize(dtype_name: str) -> int:
    return {"float64": 8, "float32": 4, "bfloat16": 2, "float16": 2}.get(
        str(dtype_name), 4)
