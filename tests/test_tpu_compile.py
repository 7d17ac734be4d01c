"""Compile rehearsal: the store's four device kernels compile for a
described TPU v5e chip at the chip smoke's geometry (``chip_smoke.py``:
default ``HoneycombConfig``, a 1M-key heap, 256-request read batches,
1,024-row delta syncs).  Nothing runs: the TPU compiler refuses here
what Mosaic would refuse on the chip — unaligned block windows, ops it
cannot lower, more VMEM than a kernel may use.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker imports
this file."""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.config import HoneycombConfig
from repro.core.schema import NodeImageLayout
from repro.kernels import delta_scatter, fused_read

CFG = HoneycombConfig()
HEAP_ROWS = 131072         # node slots of the 1M-key store
PT_LIDS = 131072           # page-table entries
BATCH = 256                # read requests per device batch
DIRTY = 1024               # rows in one delta sync / entries in one replay


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001  # honeylint: disable=no-bare-except -- any failure to describe the chip means there is nothing to compile for
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A single described chip, with the persistent compile cache off: a
    compile for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.fixture(scope="module")
def shapes(one_chip):
    layout = NodeImageLayout.for_config(CFG)

    def sds(shape, dtype=jnp.uint32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    iw = layout.image_words
    snap = (sds((HEAP_ROWS, iw)), sds((PT_LIDS,), jnp.int32),
            sds((), jnp.int32), sds((), jnp.int32),
            sds((CFG.cache_slots,), jnp.int32),
            sds((CFG.cache_slots, iw)))
    keys = (sds((BATCH, CFG.key_words)), sds((BATCH,), jnp.int32))
    return layout, sds, snap, keys


def test_fused_get_compiles(shapes):
    _, _, snap, keys = shapes
    compiled = _compile(
        lambda *a: fused_read.batched_get_fused(*a, cfg=CFG), *snap, *keys)
    # the snapshot is read in place: no image-sized temporary
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


def test_fused_scan_compiles(shapes):
    _, _, snap, keys = shapes
    compiled = _compile(
        lambda *a: fused_read.batched_scan_fused(*a, cfg=CFG),
        *snap, *keys, *keys)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


def test_image_scatter_compiles(shapes):
    layout, sds, _, _ = shapes
    _compile(delta_scatter.snapshot_image_scatter,
             sds((HEAP_ROWS, layout.image_words)), sds((DIRTY,), jnp.int32),
             sds((DIRTY, layout.image_words)))


def test_log_replay_compiles(shapes):
    layout, sds, _, _ = shapes
    offs = layout.log_replay_offsets()
    _compile(lambda *a: delta_scatter.log_replay_scatter(*a, offs=offs),
             sds((HEAP_ROWS, layout.image_words)), sds((DIRTY,), jnp.int32),
             sds((DIRTY,), jnp.int32),
             sds((DIRTY, layout.log_entry_words)))
