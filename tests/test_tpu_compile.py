"""Compile rehearsal: the store's four device kernels compile for a
described TPU v5e chip at the chip smoke's geometry (``chip_smoke.py``:
default ``HoneycombConfig``, a 1M-key heap, 256-request read batches,
1,024-row delta syncs), the GET kernel also at the YCSB configuration's
(``bench/configs/ycsb-1kb-1m.json``: 1 KB value slots).  Nothing runs:
the TPU compiler refuses here what Mosaic would refuse on the chip —
unaligned block windows, ops it cannot lower, more VMEM than a kernel
may use.  The SCAN program's lowering is pinned by a hash.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker imports
this file."""
from __future__ import annotations

import base64
import hashlib
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.config import HoneycombConfig
from repro.core.schema import NodeImageLayout
from repro.kernels import delta_scatter, fused_read

CFG = HoneycombConfig()
YCSB = HoneycombConfig(overflow_words=256)
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
    for cfg in (CFG, YCSB):
        compiled = _compile(
            lambda *a: fused_read.batched_get_fused(*a, cfg=cfg), *snap,
            *keys)
        # the snapshot is read in place: no image-sized temporary
        assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


def test_fused_scan_compiles(shapes):
    _, _, snap, keys = shapes
    compiled = _compile(
        lambda *a: fused_read.batched_scan_fused(*a, cfg=CFG),
        *snap, *keys, *keys)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


# sha256 of the fused SCAN program for the cloud configuration (default
# HoneycombConfig, 1M-key heap) at each read bucket, as ``_scan_lowering``
# prints it.  Re-pin only with a deliberate change to the SCAN kernel or
# its launcher, and say so where the change is recorded.
SCAN_LOWERING = {
    64: "754c5c15fdcddf5e95d570e636a1305743eb4550bdb2ce22b6ee9bdf4a79e469",
    128: "e1cc3259489c4c1a40b2c0ca16b46de75960cac46522b2dd011f3950e8fd7693",
    256: "cc5162396e5e778fff98449f71c593688a635b31bb943f3d502c9d057967e64b",
}


def _scan_lowering(snap, keys) -> str:
    """The lowered SCAN program's text, with the Mosaic kernel's
    serialized body replaced by the hash of its text printed without
    source locations (which name the file and line it was traced from)."""
    from jax._src.interpreters import mlir as jmlir
    from jax._src.lib.mlir import ir
    txt = jax.jit(lambda *a: fused_read.batched_scan_fused(*a, cfg=CFG)) \
        .lower(*snap, *keys, *keys).as_text()

    def body(m):
        ctx = jmlir.make_ir_context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            mod = ir.Module.parse(base64.b64decode(m.group(1)))
            asm = mod.operation.get_asm(enable_debug_info=False)
        return "body:" + hashlib.sha256(asm.encode()).hexdigest()

    return re.sub(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', body, txt)


@pytest.mark.parametrize("lanes", sorted(SCAN_LOWERING))
def test_fused_scan_lowering_pinned(shapes, lanes):
    """The GET body has its own point resolve; the SCAN body, its launcher
    and so its lowering are what they were before GET left the SCAN
    spine."""
    _, sds, snap, _ = shapes
    keys = (sds((lanes, CFG.key_words)), sds((lanes,), jnp.int32))
    text = _scan_lowering(snap, keys)
    assert text.count("body:") == 1
    got = hashlib.sha256(text.encode()).hexdigest()
    assert got == SCAN_LOWERING[lanes], got


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


@pytest.mark.parametrize("kind", ["get", "scan"])
def test_value_gather_compiles(shapes, kind):
    """``gather_values`` (core/shard.py) after a 256-lane read batch, over
    the value image of 1M slots of 1,024 B (the YCSB-C configuration): a
    plain XLA program, no temporary the size of the image."""
    from repro.core import shard
    from repro.core.read_path import GetResult, ScanResult
    _, sds, _, _ = shapes
    cfg = HoneycombConfig(overflow_words=256)
    rt = GetResult if kind == "get" else ScanResult
    width = shard._field_offsets(rt, cfg)[1]
    compiled = shard._jit_gather_values.lower(
        sds((BATCH * width + 3,)), sds((1 << 20, cfg.overflow_words)),
        result_type=rt, cfg=cfg).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2 ** 20
    # the answer and its value rows, padded to the chip's tiles
    want = 4 * (BATCH * width + 3 + BATCH * shard._value_positions(rt, cfg)
                * cfg.overflow_words)
    assert want <= mem.output_size_in_bytes < want + 2 ** 16
