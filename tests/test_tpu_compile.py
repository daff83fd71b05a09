"""The served path's chain kernels compile for a TPU v5e at real widths.

Nothing runs: each test asks the TPU compiler, for a described (not
attached) v5e chip, to compile one chain program the release server launches
on the chip, and checks that it compiled, holds a Mosaic kernel
(``tpu_custom_call``) and fits the chip.  Launch configs come from the
autotuner run against the v5e row of the device table, exactly as on the
chip.  The topology is described inside a module fixture, never at import,
so every test worker collects the same tests; the persistent compilation
cache is off around the compiles (a compile for a described chip cannot be
read back without one).
"""
import math
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.plus import PlusSchema, t_chain_factors_plus
from repro.core.residual import sub_matrix
from repro.data.tabular import synth_domain
from repro.kernels.autotune import tune_chain
from repro.kernels.kron_matvec._layout import normalize_factor
from repro.kernels.kron_matvec.fused import ChainLaunch, plan_chain
from repro.kernels.kron_matvec.ops import _build_chain_call
from repro.roofline.cost_model import DEVICE_TABLE

V5E = DEVICE_TABLE["tpu v5 lite"]
HBM_BYTES = 16 * 10 ** 9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _compile_fused(sharding, factors, dims, batch, epilogue=None):
    facs = [normalize_factor(f, n) for f, n in zip(factors, dims)]
    cfg = tune_chain(facs, dims, batch=batch, epilogue=epilogue, device=V5E,
                     persist=False)
    assert cfg.fused, f"{dims}: the v5e footprint should select the fused path"
    plan = plan_chain(facs, dims, batch=batch, block_l=cfg.block_l,
                      vmem_budget=cfg.vmem_budget, epilogue=epilogue,
                      lane_tiles=True)
    launch = ChainLaunch(plan, fused=True, interpret=False)
    return jax.jit(launch.apply).lower(
        (_spec(sharding, (plan.w_in, plan.w_out)),),
        _spec(sharding, (batch, plan.n_in))).compile()


def _assert_kernel_fits(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes)
    assert total < HBM_BYTES // 4, total


@pytest.mark.parametrize("dims,batch", [
    ((10, 10, 10), 2280),    # Synth-10^20 3-way group: 2 x 1140 cliques
    ((10, 10), 380),         # Synth-10^20 2-way group: 2 x 190 cliques
    ((42, 16, 2), 32),       # an Adult 3-way group, fused across 8 requests
])
def test_fused_chain_compiles_for_v5e(one_chip, dims, batch):
    _assert_kernel_fits(_compile_fused(
        one_chip, [sub_matrix(n) for n in dims], dims, batch))


def test_rplus_cumsum_chain_compiles_for_v5e(one_chip):
    """The Synth-10^20 range workload's 3-way reconstruction chain: T factors
    with the cumsum epilogue folded into the operator, 1140 cliques."""
    schema = PlusSchema.create(synth_domain(10, 3, kind="numeric"),
                               ["range"] * 3)
    factors = t_chain_factors_plus(schema, (0, 1, 2))
    dims = tuple(f.shape[1] for f in factors)
    _assert_kernel_fits(_compile_fused(one_chip, factors, dims, 1140,
                                       epilogue=("cumsum",) * 3))


def test_per_axis_chain_compiles_for_adult_100_cube(one_chip):
    """Adult's (100, 100, 100) clique is far past any fused footprint: its
    [v; z] pair runs through the per-axis kernel, within HBM (no lane-padded
    copy of the trailing axis)."""
    dims = (100, 100, 100)
    facs = [sub_matrix(n) for n in dims]
    cfg = tune_chain(facs, dims, batch=2, device=V5E, persist=False)
    assert not cfg.fused
    call = _build_chain_call((None, None) + tuple(f.shape for f in facs),
                             False)
    compiled = call.lower(*[_spec(one_chip, f.shape) for f in facs],
                          _spec(one_chip, (1, 2) + dims)).compile()
    _assert_kernel_fits(compiled)
    ma = compiled.memory_analysis()
    # in + out are ~8 MB each; anything near the old 4.2 GB lane-padded
    # operand would be a regression
    assert ma.temp_size_in_bytes < 64 * 2 ** 20
    assert ma.argument_size_in_bytes < 2 * 4 * math.prod(dims) * 2


def test_records_count_compiles_for_adult2(one_chip):
    """``adult2``'s records count: every <=2-way Adult table over a store of
    1,664,500 rows plus headroom, as ``RecordStore`` builds it.  The tables
    come from one Gram product of the records' one-hot block a chunk, a
    matmul on the MXU (a ``dot`` or ``convolution`` in the compiled HLO), with
    no temporary the size of a block, and the append writes the store in
    place."""
    import itertools

    from repro.core import Domain
    from repro.engine import sharded

    dom = Domain.create([100, 100, 100, 99, 85, 42, 16, 15, 9, 7, 6, 5, 2, 2])
    closure = [()] + list(itertools.chain.from_iterable(
        itertools.combinations(range(dom.n_attrs), k) for k in (1, 2)))
    metas = tuple((c, tuple(int(x) for x in s), n) for c in closure
                  for s, n in [sharded._clique_strides(dom, c)])
    rows = sharded._chunk_rows(metas[1:])
    capacity = -(-(1_664_500 + sharded.HEADROOM_ROWS) // rows) * rows

    def spec(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    count = sharded._count_program(metas, jnp.dtype(jnp.float32)).lower(
        spec((capacity, dom.n_attrs)), spec(())).compile()
    ma = count.memory_analysis()
    assert ma.temp_size_in_bytes < 64 * 2 ** 20
    assert ma.output_size_in_bytes < 2 ** 20           # 148,726 float32 cells
    hlo = count.as_text()
    assert " convolution(" in hlo or " dot(" in hlo
    write = sharded._write_rows.lower(spec((capacity, dom.n_attrs)),
                                      spec((64, dom.n_attrs)),
                                      spec(())).compile()
    assert write.memory_analysis().alias_size_in_bytes > 0
