"""Roofline machinery: hlo_stats loop-aware accounting against known-FLOPs
programs, and coherence of the committed dry-run artifacts."""
import glob
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.roofline.hlo_stats import hlo_stats
from repro.roofline.analyze import analyze_cell

ART = os.path.join(os.path.dirname(__file__), "..", "artifacts", "dryrun")


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_hlo_stats_counts_dot_flops():
    a = jnp.zeros((64, 128), jnp.float32)
    b = jnp.zeros((128, 256), jnp.float32)
    st = hlo_stats(_compiled_text(lambda a, b: a @ b, a, b))
    want = 2 * 64 * 128 * 256
    assert st["flops"] == pytest.approx(want, rel=1e-6)


def test_hlo_stats_multiplies_scan_trip_count():
    a = jnp.zeros((64, 64), jnp.float32)

    def f(x):
        def body(c, _):
            return c @ a, None
        y, _ = jax.lax.scan(body, x, None, length=17)
        return y

    st = hlo_stats(_compiled_text(f, a))
    want = 17 * 2 * 64 * 64 * 64
    assert st["flops"] == pytest.approx(want, rel=0.05)


def test_hlo_stats_nested_loops():
    a = jnp.zeros((32, 32), jnp.float32)

    def f(x):
        def outer(c, _):
            def inner(ci, _):
                return ci @ a, None
            ci, _ = jax.lax.scan(inner, c, None, length=5)
            return ci, None
        y, _ = jax.lax.scan(outer, x, None, length=3)
        return y

    st = hlo_stats(_compiled_text(f, a))
    want = 15 * 2 * 32 ** 3
    assert st["flops"] == pytest.approx(want, rel=0.05)


@pytest.mark.skipif(not os.path.isdir(ART), reason="dry-run artifacts absent")
def test_dryrun_artifacts_complete_and_coherent():
    files = glob.glob(os.path.join(ART, "*__single.json")) \
        + glob.glob(os.path.join(ART, "*__multi.json"))
    base = [f for f in files if "_fp8kv" not in f and "_kvsave" not in f
            and "_mb" not in f]
    assert len(base) == 80, f"expected 40 cells × 2 meshes, got {len(base)}"
    n_ok = n_skip = 0
    for f in base:
        with open(f) as fh:
            rec = json.load(fh)
        assert rec["status"] in ("ok", "skipped"), (f, rec.get("error"))
        if rec["status"] == "ok":
            n_ok += 1
            assert rec["memory_analysis"].get("argument_size_in_bytes", 0) > 0
            if "__single" in f:
                cell = analyze_cell(rec)
                assert cell.t_compute > 0 and cell.t_memory > 0
                assert cell.bottleneck in ("compute", "memory", "collective")
        else:
            n_skip += 1
            assert "full-attention" in rec["reason"]
    assert n_ok == 64 and n_skip == 16


# ----------------------------------------------------------- device table
def test_device_table_rows_name_their_source():
    from repro.roofline.cost_model import DEVICE_TABLE
    assert "gpu" not in DEVICE_TABLE
    v5e = DEVICE_TABLE["tpu v5 lite"]
    assert (v5e.peak_flops, v5e.hbm_bw) == (197e12, 819e9)
    for kind, spec in DEVICE_TABLE.items():
        assert spec.source, kind


@pytest.mark.parametrize("kind", ["TPU v5 lite", "tpu v5e", "TPU v4", "cpu"])
def test_device_spec_known_kinds(kind):
    from repro.roofline.cost_model import device_spec
    assert device_spec(kind).kind in kind.lower() or kind == "tpu v5e"


@pytest.mark.parametrize("kind", ["TPU v99", "NVIDIA H100", "tpu"])
def test_device_spec_unknown_kind_raises(kind):
    from repro.roofline.cost_model import device_spec
    with pytest.raises(KeyError):
        device_spec(kind)


def test_detect_device_raises_when_backend_fails(monkeypatch):
    from repro.roofline import cost_model

    def broken():
        raise RuntimeError("no backend")

    monkeypatch.setattr(jax, "devices", broken)
    with pytest.raises(RuntimeError):
        cost_model.detect_device(refresh=True)
    monkeypatch.undo()
    assert cost_model.detect_device(refresh=True).kind == "cpu"
