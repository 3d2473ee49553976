"""Compile for a described TPU v5e (2x2), without a chip.

What the chip's compiler refuses shows up here at no chip time: the served
XLA executors must compile at the chip smoke's real widths and fit one
chip's HBM, and every Pallas kernel entry point is pinned as refused
(strict xfail carrying the compiler's message), so a kernel repair turns
its case into a visible XPASS failure until the mark is dropped.

The topology is described only inside the module fixture: the TPU library
may be loaded by one process at a time, and every test worker imports this
file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import binning
from repro.core import plan as plan_mod
from repro.core.csr import CSRDevice
from repro.kernels import accumulator, flop_per_row, ops
from repro.kernels import spgemm_numeric, spgemm_symbolic

HBM_BYTES = 16 * 10**9       # one v5e chip
M = 1 << 20                  # chip_smoke.py's er operand rows
CAP = 1 << 24                # its pow2 device-CSR capacity (8.4M nnz)
POP = 1 << 19                # its widest bucket's pow2 population
DEG_A, DEG_B, ROW_CAP = 8, 32, 128   # that bucket's degree bounds, capacity


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
            try:
                desc = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh(topo):
    import numpy as np
    return Mesh(np.array(topo.devices), ("data",))


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _operand(sharding, rows=M, cap=CAP):
    return CSRDevice(rpt=_sds((rows + 1,), jnp.int32, sharding),
                     col=_sds((cap,), jnp.int32, sharding),
                     val=_sds((cap,), jnp.float32, sharding),
                     shape=(rows, M))


def _meta(deg_a=DEG_A, deg_b=DEG_B, cap=ROW_CAP):
    blk = binning._pick_block_rows(deg_a * deg_b,
                                   binning.DEFAULT_LANE_BUDGET,
                                   binning.DEFAULT_MAX_BLOCK_ROWS)
    return (deg_a, deg_b, blk, binning.ROUTE_ESC, 0, 0, 0, cap)


def _device_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


def test_local_executor_compiles_at_smoke_width(one_chip):
    """The masked (pop-quant) local executor the service dispatches, at the
    smoke's widest er bucket: compiles, fits HBM, and is pure XLA."""
    run = plan_mod._build_local_executor((_meta(),), ROW_CAP, False,
                                         plan_mod.PlanCache(), masked=True)
    rows = _sds((POP,), jnp.int32, one_chip)
    mask = _sds((POP,), jnp.bool_, one_chip)
    compiled = run.lower(_operand(one_chip), _operand(one_chip), rows, mask,
                         rows).compile()
    assert 0 < _device_bytes(compiled) < HBM_BYTES
    assert "tpu_custom_call" not in compiled.as_text()


def test_dist_executor_compiles_on_four_chips(mesh):
    """Row-sharded executor: replicated operands, sharded row tables."""
    rep = NamedSharding(mesh, P())
    run = plan_mod._build_dist_executor((_meta(),), mesh, "data", False,
                                        plan_mod.PlanCache())
    table = _sds((4, POP // 4), jnp.int32, NamedSharding(mesh, P("data", None)))
    compiled = run.lower(_operand(rep), _operand(rep), table).compile()
    assert 0 < _device_bytes(compiled) < HBM_BYTES


def test_panel_dist_executor_compiles_on_four_chips(mesh):
    """2 row shards × 2 column panels folded onto the 4-device axis: each
    device gets its gathered panel operand, A's index/values replicated."""
    rep = NamedSharding(mesh, P())
    shd = NamedSharding(mesh, P("data", None))
    nref, ecap = M, CAP // 2
    run = plan_mod._build_panel_dist_executor(
        (_meta(),), (M, M), nref, M, mesh, "data", False,
        plan_mod.PlanCache())
    compiled = run.lower(
        _sds((M + 1,), jnp.int32, rep), _sds((CAP,), jnp.float32, rep),
        _sds((4, CAP), jnp.int32, shd), _sds((4, nref + 1), jnp.int32, shd),
        _sds((4, ecap), jnp.int32, shd), _sds((4, ecap), jnp.float32, shd),
        _sds((4, POP // 2), jnp.int32, shd)).compile()
    assert 0 < _device_bytes(compiled) < HBM_BYTES


# --------------------------------------------------------------------------- #
# Pallas kernel entry points: each is refused today, for the reason given.
# --------------------------------------------------------------------------- #
ANY_LOAD = "Loads are only allowed on VMEM and SMEM references"
INT_INDEX = "Cannot do int indexing on TPU"
RANK1_BLOCK = r"rank 1 block shapes.*multiple of the tiling size \(128"

R = 4096                     # rows / samples per kernel call
SPAN = 4096
KERNELS = {
    "flop_per_row_pallas": (
        flop_per_row.flop_per_row_pallas, "rpt col rownnz",
        dict(block_rows=256, max_deg_a=128), ANY_LOAD),
    "flop_rows_pallas": (
        flop_per_row.flop_rows_pallas, "rpt col rownnz rows",
        dict(block_rows=256, max_deg_a=DEG_A), INT_INDEX),
    "sampled_symbolic_pallas": (
        spgemm_symbolic.sampled_symbolic_pallas, "rpt col rpt col rows",
        dict(max_deg_a=DEG_A, max_deg_b=DEG_B), RANK1_BLOCK),
    "fused_flop_symbolic_pallas": (
        spgemm_symbolic.fused_flop_symbolic_pallas, "rpt col rpt col rows",
        dict(max_deg_a=DEG_A, max_deg_b=DEG_B), RANK1_BLOCK),
    "bitmask_symbolic_pallas": (
        accumulator.bitmask_symbolic_pallas, "rpt col rpt col rows",
        dict(max_deg_a=DEG_A, max_deg_b=DEG_B, ncols_b=M, span=SPAN),
        RANK1_BLOCK),
    "fused_flop_symbolic_bitmask_pallas": (
        accumulator.fused_flop_symbolic_bitmask_pallas,
        "rpt col rpt col rows",
        dict(max_deg_a=DEG_A, max_deg_b=DEG_B, ncols_b=M, span=SPAN),
        RANK1_BLOCK),
    "spgemm_numeric_pallas": (
        spgemm_numeric.spgemm_numeric_pallas, "rpt col val rpt col val rows",
        dict(max_deg_a=DEG_A, max_deg_b=DEG_B), RANK1_BLOCK),
    "spa_numeric_pallas": (
        accumulator.spa_numeric_pallas, "rpt col val rpt col val rows",
        dict(max_deg_a=DEG_A, max_deg_b=DEG_B, ncols_b=M, tile_n=1024,
             n_tiles=1), RANK1_BLOCK),
    "bin_numeric_pallas": (
        accumulator.bin_numeric_pallas, "rpt col val rpt col val rows",
        dict(max_deg_a=DEG_A, max_deg_b=DEG_B, ncols_b=M, tile_n=256,
             n_tiles=4), RANK1_BLOCK),
}


def test_refused_kernel_list_matches_compiler_cases():
    """``use_kernel=True`` on TPU names exactly the kernels pinned below."""
    assert set(ops.TPU_REFUSED_KERNELS) == set(KERNELS)


@pytest.mark.parametrize("name", [
    pytest.param(name, marks=pytest.mark.xfail(
        strict=True, raises=ValueError,
        reason=f"the TPU compiler refuses it: {refusal}"))
    for name, (_, _, _, refusal) in KERNELS.items()])
def test_pallas_kernel_compiles(name, one_chip):
    fn, argspec, kw, refusal = KERNELS[name]
    shapes = dict(rpt=((M + 1,), jnp.int32), col=((CAP,), jnp.int32),
                  val=((CAP,), jnp.float32), rownnz=((M,), jnp.int32),
                  rows=((R,), jnp.int32))
    args = [_sds(*shapes[k], one_chip) for k in argspec.split()]
    try:
        fn.lower(*args, interpret=False, **kw).compile()
    except ValueError as e:
        # any other refusal is a new finding, not this pinned one
        assert re.search(refusal, str(e)), str(e)[:500]
        raise
