"""The read batch's one device->host copy: each GET/SCAN batch comes back
as one packed u32 buffer (``_pack_read``), split on the host into
zero-copy views (``_unpack_read``).  Answers, fallbacks, overflow values,
cache meters and the copy counters are checked through the shard, on the
fused and the reference path, for a full and a padded batch."""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import HoneycombConfig
from repro.core import shard as shard_mod
from repro.core.config import bucket_pow2
from repro.core.keys import int_key, pack_keys
from repro.core.read_path import (GetResult, ScanResult, batched_get,
                                  batched_scan)
from repro.core.shard import StoreShard
from repro.kernels import ops as kernel_ops

SMALL = HoneycombConfig(node_cap=16, log_cap=4, n_shortcuts=4,
                        cache_slots=32, max_scan_leaves=2,
                        max_scan_items=16, max_height=6)
N = 120


def _value(i: int) -> bytes:
    """Every fifth value is longer than the 16 inline bytes: the batch
    gathers it from the snapshot's value image into its one copy."""
    return b"L%06d" % i * 4 if i % 5 == 0 else b"v%06d" % i


def _shard(backend: str) -> StoreShard:
    s = StoreShard(dataclasses.replace(SMALL, read_backend=backend),
                   heap_capacity=256)
    for i in range(N):
        s.put(int_key(i), _value(i))
    for i in range(0, N, 13):
        s.delete(int_key(i))
    return s


def _requests(op: str, lanes: int):
    """``lanes`` requests holding at least one overflow value and, for a
    SCAN, one range longer than ``max_scan_items`` (a host fallback)."""
    if op == "get":
        ids = [5, 6, 13, 500, 40, 41, 99, 100][:lanes]
        return [int_key(i) for i in ids]
    ranges = [(int_key(3), int_key(7)), (int_key(0), int_key(70)),
              (int_key(9), int_key(12)), (int_key(24), int_key(31)),
              (int_key(60), int_key(66)), (int_key(200), int_key(210)),
              (int_key(88), int_key(95)), (int_key(114), int_key(119))]
    return ranges[:lanes]


def _args(op: str, reqs, cfg):
    """The batch as the shard uploads it: padded to its bucket, packed."""
    pad = reqs + [reqs[0]] * (bucket_pow2(len(reqs)) - len(reqs))
    if op == "scan":
        pad = [r[0] for r in pad] + [r[1] for r in pad]
    lanes, lens = pack_keys(pad, cfg.key_words)
    if op == "get":
        return [jnp.asarray(lanes), jnp.asarray(lens)]
    n = len(pad) // 2
    return [jnp.asarray(a) for a in (lanes[:n], lens[:n],
                                     lanes[n:], lens[n:])]


def _direct(op: str, backend: str, snap, args, cfg):
    """The same batch through the kernel entry point itself, unpacked:
    (result, meters)."""
    if backend == "fused":
        fn = (kernel_ops.batched_get_fused if op == "get"
              else kernel_ops.batched_scan_fused)
        return fn(snap, *args, cfg=cfg, lb_fraction=cfg.lb_fraction)
    fn = batched_get if op == "get" else batched_scan
    return fn(snap, *args, cfg=cfg), np.zeros(3, np.int32)


@pytest.mark.parametrize("lanes", [8, 5], ids=["full", "ragged"])
@pytest.mark.parametrize("backend", ["fused", "reference"])
@pytest.mark.parametrize("op", ["get", "scan"])
def test_packed_read_batch(op, backend, lanes):
    s = _shard(backend)
    snap = s.export_snapshot()
    assert s._read_backend_for(snap) == backend
    reqs = _requests(op, lanes)
    ps, cs = s.pipeline_stats, s.cache_stats
    before = (ps.read_batches, ps.read_copies, ps.host_scans,
              cs.vmem_hits, cs.heap_gathers, cs.lb_routed)
    if op == "get":
        got = s.get_batch(reqs)
        assert got == [s.get(k) for k in reqs]
        assert got[0] == _value(5) and len(got[0]) > 16   # value image
    else:
        got = s.scan_batch(reqs)
        assert got == [s.tree.scan(lo, hi) for lo, hi in reqs]
        assert len(got[1]) > SMALL.max_scan_items         # host fallback
        assert (int_key(5), _value(5)) in got[0]          # value image
    args = _args(op, reqs, s.cfg)
    res, meters = _direct(op, backend, snap, args, s.cfg)
    after = (ps.read_batches, ps.read_copies, ps.host_scans,
             cs.vmem_hits, cs.heap_gathers, cs.lb_routed)
    step = [a - b for a, b in zip(after, before)]
    assert step[:3] == [1, 1, 1 if op == "scan" else 0]
    assert step[3:] == np.asarray(meters).tolist()
    # the shard's entry point answers bit for bit what the kernel does
    jit_fn = {("get", "fused"): shard_mod._jit_get_fused,
              ("scan", "fused"): shard_mod._jit_scan_fused,
              ("get", "reference"): shard_mod._jit_get,
              ("scan", "reference"): shard_mod._jit_scan}[op, backend]
    rtype = GetResult if op == "get" else ScanResult
    kw = {"lb_fraction": s.cfg.lb_fraction} if backend == "fused" else {}
    buf = np.asarray(jit_fn(snap, *args, cfg=s.cfg, **kw))
    views, got_meters = shard_mod._unpack_read(buf, rtype, s.cfg)
    assert np.shares_memory(views[1], buf)                # views, no copy
    for name, v, d in zip(rtype._fields, views, res):
        d = np.asarray(d)
        assert v.shape == d.shape, name
        assert np.array_equal(v.astype(d.dtype), d), name
    assert got_meters.tolist() == np.asarray(meters).tolist()


@pytest.mark.parametrize("jit_name,module", [
    ("_jit_get_fused", "jit_batched_get_fused"),
    ("_jit_scan_fused", "jit_batched_scan_fused"),
    ("_jit_get", "jit_batched_get"),
    ("_jit_scan", "jit_batched_scan")])
def test_read_programs_keep_their_names(jit_name, module):
    """bench/metrics/read_kernel_us_per_req.py finds the read kernel's
    device time by these module names (``READ_MODULES``)."""
    s = _shard("fused")
    snap = s.export_snapshot()
    op = "get" if "get" in jit_name else "scan"
    kw = {"lb_fraction": 0.0} if "fused" in jit_name else {}
    lowered = getattr(shard_mod, jit_name).lower(
        snap, *_args(op, _requests(op, 8), s.cfg), cfg=s.cfg, **kw)
    assert f"module @{module} " in lowered.as_text()
