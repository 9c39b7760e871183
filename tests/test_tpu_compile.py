"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Nothing runs here: the TPU compiler installed beside JAX compiles each
kernel for a chip that is described, not attached, and raises what the
chip's compiler would raise (unaligned slices, scoped-VMEM overflow,
unsupported lowering).  Interpret mode accepts all of those, so these
tests are what keeps the kernels compilable between chip runs.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and every
test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import grid_graph, partition_graph, with_csc_layout
from repro.kernels.frontier import (frontier_expand_batched_pallas,
                                    frontier_expand_node_blocked_pallas,
                                    pallas_supported)
from repro.kernels.frontier.kernel import flat_block_e
from repro.kernels.stopcheck import stopcheck_pallas

GRID_SIDE = 512                     # 2^18 vertices: the chip smoke's road


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def road():
    return grid_graph(GRID_SIDE, GRID_SIDE)


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _flat_limit(batch: int) -> int:
    """Largest vertex count the flat kernel's fit check accepts."""
    lo, hi = 1, 1 << 16
    while lo < hi:
        mid = (lo + hi + 1) // 2
        lo, hi = (mid, hi) if pallas_supported(mid, batch) else (lo, mid - 1)
    return lo


@pytest.mark.parametrize("batch", [8, 64])
def test_flat_kernel_compiles_at_its_fit_limit(one_chip, batch):
    n = _flat_limit(batch)
    assert not pallas_supported(n + 1, batch)
    block_e = flat_block_e(n, batch)
    e = 16 * block_e
    f = jax.jit(lambda s, d, di, si, lv: frontier_expand_batched_pallas(
        s, d, di, si, lv, interpret=False))
    compiled = f.lower(_sds((e,), jnp.int32, one_chip),
                       _sds((e,), jnp.int32, one_chip),
                       _sds((n + 1, batch), jnp.int32, one_chip),
                       _sds((n + 1, batch), jnp.float32, one_chip),
                       _sds((batch,), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("batch", [8, 64])
def test_node_blocked_kernel_compiles_on_road_layout(one_chip, road, batch):
    csc = with_csc_layout(road, batch=batch).csc      # default blocking
    rows = csc.v_pad
    f = jax.jit(lambda c, di, si, lv: frontier_expand_node_blocked_pallas(
        c, di, si, lv, interpret=False))
    compiled = f.lower(_shapes(csc, one_chip),
                       _sds((rows, batch), jnp.int32, one_chip),
                       _sds((rows, batch), jnp.float32, one_chip),
                       _sds((batch,), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("batch", [8, 64])
def test_node_blocked_wide_state_compiles_on_road_shard(one_chip, road,
                                                        batch):
    """The sharded lane's kernel: one shard's local layout over the
    all-gathered global state of a 4-way partition."""
    pg = partition_graph(road, 4, batch=batch)
    local = pg.shards.shard(0)
    f = jax.jit(lambda c, di, si, lv: frontier_expand_node_blocked_pallas(
        c, di, si, lv, interpret=False, wide_state=True))
    compiled = f.lower(_shapes(local, one_chip),
                       _sds((pg.shards.v_pad, batch), jnp.int32, one_chip),
                       _sds((pg.shards.v_pad, batch), jnp.float32, one_chip),
                       _sds((batch,), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_stopcheck_kernel_compiles(one_chip):
    v = GRID_SIDE * GRID_SIDE
    vec = _sds((v,), jnp.float32, one_chip)
    scalar = _sds((), jnp.float32, one_chip)
    f = jax.jit(lambda c, t, dl, du, om: stopcheck_pallas(
        c, t, dl, du, om, interpret=False))
    compiled = f.lower(vec, scalar, vec, vec, scalar).compile()
    assert "tpu_custom_call" in compiled.as_text()
