"""Autotuner, cost model, dtype-aware planning and tuning-cache tests
(docs/DESIGN.md §14, docs/TUNING.md)."""
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.kron import kron_matvec_np
from repro.kernels.autotune import (TuningCache, autotune_mode, chain_key,
                                    pretune, registry_snapshot,
                                    reset_registry, resolve_config,
                                    tune_chain)
from repro.kernels.autotune.cache import CACHE_VERSION
from repro.kernels.kron_matvec.fused import fused_chain_matvec, plan_chain
from repro.roofline.cost_model import DEVICE_TABLE, CostModel, DeviceSpec


@pytest.fixture(autouse=True)
def _isolated_tuner(tmp_path, monkeypatch):
    """Every test sees a fresh registry and a throwaway on-disk cache."""
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "att"))
    reset_registry()
    yield
    reset_registry()


def _mode_on(monkeypatch):
    """Tests asserting tuner activity force a tuning mode when the ambient
    env (e.g. an off-mode CI shard) disabled it."""
    if autotune_mode() == "off":
        monkeypatch.setenv("REPRO_KERNEL_AUTOTUNE", "model")


def _rand_chain(rng, n_axes, sizes):
    dims = tuple(int(s) for s in sizes[:n_axes])
    facs = []
    for n in dims:
        if rng.random() < 0.25:
            facs.append(None)                       # identity axis
        else:
            m = int(rng.integers(1, n + 1))
            facs.append(rng.standard_normal((m, n)))
    return facs, dims


# --------------------------------------------------------------- bit-exactness
@settings(deadline=None, max_examples=12)
@given(st.integers(1, 3), st.tuples(st.integers(2, 12), st.integers(2, 12),
                                    st.integers(2, 12)),
       st.integers(1, 40), st.integers(0, 2 ** 31 - 1))
def test_tuned_fp32_bit_identical_to_default(n_axes, sizes, batch, seed):
    """Rows are independent under any block_l/padding: the tuned fp32 launch
    must be BIT-identical to the untuned default, not merely close."""
    rng = np.random.default_rng(seed)
    facs, dims = _rand_chain(rng, n_axes, sizes)
    n_in = int(np.prod(dims))
    x = rng.standard_normal((batch, n_in)).astype(np.float32)
    y_default = np.asarray(fused_chain_matvec(
        facs, x, dims, block_l=None, vmem_budget=None))   # explicit: no tuner
    cfg = tune_chain(facs, dims, batch=batch)
    y_tuned = np.asarray(fused_chain_matvec(
        facs, x, dims, block_l=cfg.block_l, vmem_budget=cfg.vmem_budget))
    assert np.array_equal(y_default, y_tuned)


def test_resolved_path_bit_identical_to_off(monkeypatch):
    rng = np.random.default_rng(7)
    facs, dims = _rand_chain(rng, 3, (5, 4, 6))
    x = rng.standard_normal((11, int(np.prod(dims)))).astype(np.float32)
    monkeypatch.setenv("REPRO_KERNEL_AUTOTUNE", "off")
    y_off = np.asarray(fused_chain_matvec(facs, x, dims))
    monkeypatch.setenv("REPRO_KERNEL_AUTOTUNE", "model")
    y_on = np.asarray(fused_chain_matvec(facs, x, dims))
    assert np.array_equal(y_off, y_on)


# ------------------------------------------------------------- mixed precision
def _oracle(facs, dims, x):
    full = [np.eye(n) if f is None else np.asarray(f, np.float64)
            for f, n in zip(facs, dims)]
    return np.stack([kron_matvec_np(full, row.astype(np.float64), dims)
                     for row in x])


def test_bf16_compute_fp32_accumulate_bounded_drift():
    rng = np.random.default_rng(3)
    dims = (6, 5, 4)
    facs = [rng.standard_normal((4, 6)), None, rng.standard_normal((3, 4))]
    x = rng.standard_normal((9, 120)).astype(np.float32)
    ref = _oracle(facs, dims, x)
    y = np.asarray(fused_chain_matvec(facs, x, dims, block_l=16,
                                      compute_dtype="bfloat16"))
    assert y.dtype == np.float32
    scale = np.abs(ref).max()
    # bf16 has 8 mantissa bits (~4e-3 ulp); fp32 accumulation keeps the
    # error at the operand-rounding level instead of growing with depth.
    assert np.abs(y - ref).max() / scale < 3e-2


def test_fp16_compute_fp32_accumulate_bounded_drift():
    rng = np.random.default_rng(4)
    dims = (5, 7)
    facs = [rng.standard_normal((5, 5)), rng.standard_normal((4, 7))]
    x = rng.standard_normal((6, 35)).astype(np.float32)
    ref = _oracle(facs, dims, x)
    y = np.asarray(fused_chain_matvec(facs, x, dims, block_l=16,
                                      compute_dtype="float16"))
    scale = np.abs(ref).max()
    assert np.abs(y - ref).max() / scale < 4e-3   # 10 mantissa bits


def test_plan_rejects_unknown_compute_dtype():
    with pytest.raises((ValueError, TypeError)):
        plan_chain([np.ones((2, 3))], (3,), compute_dtype="int8")


# ------------------------------------------------------ itemsize-aware VMEM
def test_vmem_accounting_is_itemsize_correct():
    rng = np.random.default_rng(0)
    facs = [rng.standard_normal((3, 4)), rng.standard_normal((5, 5))]
    dims = (4, 5)
    p32 = plan_chain(facs, dims, batch=16, block_l=16)
    pbf = plan_chain(facs, dims, batch=16, block_l=16,
                     compute_dtype="bfloat16")
    # Same block: the bf16 input tile and factors halve; fp32 accumulator
    # tiles stay — strictly smaller, but not half.
    assert pbf.vmem_bytes < p32.vmem_bytes
    assert pbf.vmem_bytes > p32.vmem_bytes // 2
    assert pbf.signature != p32.signature          # dtype is a jit-cache key


def test_tril_epilogue_accounted_at_compute_dtype():
    facs = [np.ones((4, 4))]
    base32 = plan_chain(facs, (4,), batch=16, block_l=16)
    epi32 = plan_chain(facs, (4,), batch=16, block_l=16,
                       epilogue=("cumsum",))
    basebf = plan_chain(facs, (4,), batch=16, block_l=16,
                        compute_dtype="bfloat16")
    epibf = plan_chain(facs, (4,), batch=16, block_l=16,
                       epilogue=("cumsum",), compute_dtype="bfloat16")
    # The cumsum folds into the dense chain operator, which is already
    # accounted at the compute dtype: the epilogue adds no VMEM of its own.
    assert epi32.vmem_bytes == base32.vmem_bytes
    assert epibf.vmem_bytes == basebf.vmem_bytes
    assert basebf.vmem_bytes < base32.vmem_bytes


# ------------------------------------------------------------------ cost model
def test_cost_model_bytes_monotone_in_block_l():
    model = CostModel(DEVICE_TABLE["cpu"])
    facs = [np.ones((3, 4)), np.ones((2, 5))]
    dims = (4, 5)
    last = -1.0
    for bl in (8, 16, 32, 64, 128):
        plan = plan_chain(facs, dims, batch=20, block_l=bl,
                          vmem_budget=1 << 30)
        cost = model.chain_cost(plan, batch=20)
        # Padded-batch traffic never shrinks as the block grows (20 rows pad
        # to 24, 32, ..., 128): rounding waste is visible to the tuner.
        assert cost.hbm_bytes >= last
        last = cost.hbm_bytes
    p24 = model.chain_cost(plan_chain(facs, dims, batch=20, block_l=24,
                                      vmem_budget=1 << 30), batch=20)
    p128 = model.chain_cost(plan_chain(facs, dims, batch=20, block_l=128,
                                       vmem_budget=1 << 30), batch=20)
    assert p24.hbm_bytes < p128.hbm_bytes


def test_fused_never_chosen_when_tile_exceeds_device_limit():
    tiny = DeviceSpec("tiny", peak_flops=1e12, peak_flops_f32=1e12,
                      hbm_bw=1e11, ici_bw=1e10, vmem_limit=1024,
                      default_vmem_budget=1024, step_overhead_s=1e-6)
    rng = np.random.default_rng(1)
    facs = [rng.standard_normal((64, 64))]
    cfg = tune_chain(facs, (64,), batch=32, device=tiny, persist=False)
    assert cfg.fused is False


def test_tuner_minimizes_grid_steps_in_interpret_mode():
    """On CPU (interpret) the per-step Python overhead dominates: the tuner
    must pick the exact-padded-batch block (grid == 1), not the 128 default
    (18 steps for the Synth-10^20 3-way group's 2280 lanes)."""
    rng = np.random.default_rng(2)
    facs = [rng.standard_normal((1, 20))] * 3
    cfg = tune_chain(facs, (20, 20, 20), batch=2280,
                     device=DEVICE_TABLE["cpu"], persist=False)
    assert cfg.fused
    assert cfg.grid_steps == 1
    assert cfg.block_l == 2280


# ---------------------------------------------------------------- cache + env
def test_tuning_cache_round_trip(tmp_path):
    c = TuningCache("cpu", path=str(tmp_path / "t.json"))
    c.put("k1", {"block_l": 64, "vmem_budget": 123, "fused": True})
    c2 = TuningCache("cpu", path=str(tmp_path / "t.json"))
    assert c2.get("k1")["block_l"] == 64
    assert c2.get("nope") is None


def test_tuning_cache_concurrent_puts(tmp_path):
    """Racing puts must not lose entries (lazy load + mutate is locked)."""
    import threading
    c = TuningCache("cpu", path=str(tmp_path / "t.json"))
    threads = [threading.Thread(target=c.put, args=(f"k{i}", {"block_l": 8}))
               for i in range(16)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(c.load()) == 16


def test_tuning_cache_invalidation(tmp_path):
    path = str(tmp_path / "t.json")
    TuningCache("cpu", path=path).put("k", {"block_l": 64})
    # another device kind: whole file invalid
    assert TuningCache("tpu v5 lite", path=path).get("k") is None
    # version bump: whole file invalid
    import json
    with open(path) as fh:
        blob = json.load(fh)
    blob["version"] = CACHE_VERSION + 1
    with open(path, "w") as fh:
        json.dump(blob, fh)
    assert TuningCache("cpu", path=path).get("k") is None
    # corrupt file: empty cache, no raise
    with open(path, "w") as fh:
        fh.write("{not json")
    assert TuningCache("cpu", path=path).get("k") is None


def test_resolve_config_hits_disk_cache_after_registry_reset(monkeypatch):
    _mode_on(monkeypatch)
    rng = np.random.default_rng(5)
    facs = [rng.standard_normal((2, 6))]
    cfg = tune_chain(facs, (6,), batch=10)          # persists
    reset_registry()
    got = resolve_config(facs, (6,), batch=10)
    assert got is not None
    assert got.source == "cache"
    assert got.block_l == cfg.block_l


def test_mode_env_gate(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_AUTOTUNE", "off")
    assert autotune_mode() == "off"
    assert resolve_config([np.ones((2, 3))], (3,), batch=4) is None
    monkeypatch.setenv("REPRO_KERNEL_AUTOTUNE", "bogus")
    assert autotune_mode() == "model"              # unknown → default
    monkeypatch.setenv("REPRO_KERNEL_AUTOTUNE", "measure")
    assert autotune_mode() == "measure"


def test_off_mode_keeps_untuned_default_plan():
    plan = plan_chain([np.ones((2, 5))], (5,), batch=40)
    assert plan.block_l == min(128, 40)            # pad_to(40, 8) == 40
    assert plan.compute_dtype == "float32"
    assert plan.block_l % 8 == 0


def test_measure_mode_refines_and_tags_source():
    rng = np.random.default_rng(6)
    facs = [rng.standard_normal((3, 4)), rng.standard_normal((2, 5))]
    cfg = tune_chain(facs, (4, 5), batch=24, mode="measure", persist=False)
    assert cfg.source == "measure"
    assert cfg.predicted_s > 0


def test_chain_key_discriminates():
    f = [(2, 3)]
    k1 = chain_key("cpu", (3,), f, None, 8)
    assert k1 != chain_key("cpu", (3,), f, None, 16)          # batch
    assert k1 != chain_key("cpu", (3,), [None], None, 8)      # factor shape
    assert k1 != chain_key("cpu", (3,), f, ("cumsum",), 8)    # epilogue
    assert k1 != chain_key("tpu v5 lite", (3,), f, None, 8)   # device


# ---------------------------------------------------------- engine integration
def _plan(sizes=(3, 4, 5)):
    from repro.core import Domain, MarginalWorkload, select_sum_of_variances
    dom = Domain.create(list(sizes))
    cliques = tuple((i, j) for i in range(len(sizes))
                    for j in range(i + 1, len(sizes)))
    return select_sum_of_variances(MarginalWorkload(dom, cliques), 10.0)


def test_engine_registers_tuned_chains(monkeypatch):
    _mode_on(monkeypatch)
    from repro.engine import MarginalEngine
    eng = MarginalEngine(_plan(), use_kernel=True)
    assert eng.stats.tuned_chains == len(eng.chain_plans())
    assert eng.stats.fallback_chains == 0
    for row in eng.chain_plans():
        assert row["compute_dtype"] == "float32"
        assert row["tuned"] is True
        assert row["tune_source"] in ("model", "measure", "cache")
        assert row["intensity"] is not None
    snap = registry_snapshot()
    assert len(snap["entries"]) >= len(eng.chain_plans())
    assert snap["mode"] in ("model", "measure")


def test_engine_off_mode_untouched(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_AUTOTUNE", "off")
    from repro.engine import MarginalEngine
    eng = MarginalEngine(_plan((3, 4)), use_kernel=True)
    assert eng.stats.tuned_chains == 0
    for row in eng.chain_plans():
        assert row["tuned"] is False
        assert row["tune_source"] == "default"


def test_pretune_batch():
    rng = np.random.default_rng(8)
    chains = [([rng.standard_normal((2, 4))], (4,), 6, None),
              ([rng.standard_normal((3, 5))], (5,), 12, None)]
    out = pretune(chains)
    assert len(out) == 2
    assert all(c.block_l % 8 == 0 for c in out)


def test_server_stats_surface_kernels_and_autotune(tmp_path):
    from repro.serve import BudgetLedger, ReleaseServer
    ledger = BudgetLedger(str(tmp_path / "ledger.jsonl"), fsync=False)
    srv = ReleaseServer(ledger).start()
    try:
        srv.register_tenant("t1", _plan((3, 4)), rho=10.0)
        d = srv.stats_dict()
        assert "pallas_calls" in d["kernels"]
        assert d["autotune"]["mode"] in ("off", "model", "measure")
        assert isinstance(d["autotune"]["entries"], dict)
    finally:
        srv.stop()


def test_narrow_clamped_without_allow_narrow(monkeypatch):
    """A tuned narrow dtype never reaches a noise-carrying call site."""
    _mode_on(monkeypatch)
    monkeypatch.setenv("REPRO_KERNEL_COMPUTE_DTYPES", "float32,bfloat16")
    rng = np.random.default_rng(9)
    facs = [rng.standard_normal((3, 4))]
    dims = (4,)
    tune_chain(facs, dims, batch=8, dtypes=("bfloat16",))
    x = rng.standard_normal((8, 4)).astype(np.float32)
    y_clamped = np.asarray(fused_chain_matvec(facs, x, dims))
    y_fp32 = np.asarray(fused_chain_matvec(facs, x, dims, block_l=8,
                                           compute_dtype="float32"))
    assert np.array_equal(y_clamped, y_fp32)
