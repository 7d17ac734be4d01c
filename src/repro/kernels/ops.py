"""Jit'd dispatch wrappers for the Pallas kernels.

``backend`` selects the implementation:
  "ref"       pure-jnp oracle — what XLA:CPU lowers (dry-run / CI default)
  "interpret" Pallas kernel body executed in Python on CPU (correctness)
  "pallas"    compiled Pallas kernel — real TPUs

The default follows the runtime: TPU -> pallas, else ref.  It is decided
at dispatch, never at import: importing the store must not initialise a
JAX backend (that claims the chip for the importing process).  On a TPU
the store's read and sync kernels (delta/image/log-replay scatters, fused
GET/SCAN) run compiled Pallas only — asking them for "ref" or "interpret"
there is an error, and a lowering error surfaces as is.
"""
from __future__ import annotations

import collections
import functools

import jax

from . import delta_scatter as _ds
from . import fused_read as _fr
from . import key_search as _ks
from . import leaf_merge as _lm
from . import paged_attention as _pa
from . import ref as _ref


def default_backend() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _store_backend(backend: str | None) -> str:
    """Resolve a store read/sync kernel backend; the CPU paths exist for
    the tests only and are refused on a TPU."""
    backend = backend or default_backend()
    if backend != "pallas" and jax.default_backend() == "tpu":
        raise ValueError(f"store kernels run compiled Pallas on a TPU; "
                         f"backend={backend!r} is a CPU test path")
    return backend


# ---------------------------------------------------------------- dispatch
# counter: device launches per read batch, recorded at the NON-jitted shard
# dispatch site (core/shard.py) so trace-caching can't hide repeats.  The
# counts are the analytic launch model the latency benchmark pins (like
# PR 6's dma/node 24 -> 1): the fused megakernels execute the whole
# traversal in ONE pallas_call, where the reference path issues one
# gather/merge stage per descend level plus one per scan-leaf visit (floor
# pre-pass + forward pass) and GET adds its equality post-pass.
READ_DISPATCHES: collections.Counter = collections.Counter()


def read_dispatch_count(op: str, read_backend: str, cfg) -> int:
    """Device dispatches one ``op`` ("get"/"scan") batch costs under
    ``read_backend`` ("fused"/"reference") at this config's static
    traversal bounds."""
    if read_backend == "fused":
        return 1
    n = cfg.max_height + 2 * cfg.max_scan_leaves
    return n + 1 if op == "get" else n


def record_read_dispatch(op: str, read_backend: str, cfg, batches: int = 1):
    """Meter ``batches`` read-batch dispatches (called per device call by
    the shard layer)."""
    READ_DISPATCHES[(op, read_backend)] += \
        batches * read_dispatch_count(op, read_backend, cfg)
    READ_DISPATCHES[("batches", op, read_backend)] += batches


def reset_read_dispatches():
    READ_DISPATCHES.clear()


def read_dispatch_stats() -> dict:
    """Per-(op, backend) dispatched-launch totals and per-batch averages."""
    out = {}
    for op in ("get", "scan"):
        for rb in ("fused", "reference"):
            b = READ_DISPATCHES.get(("batches", op, rb), 0)
            d = READ_DISPATCHES.get((op, rb), 0)
            if b:
                out[f"{op}_{rb}"] = {"batches": b, "dispatches": d,
                                     "per_batch": d / b}
    return out


def collect() -> list:
    """Telemetry source for the launch meter (core/telemetry.py collect
    protocol).  Returns plain ``(name, kind, value, labels)`` tuples —
    kernels must not import repro.core (core imports kernels), so the
    registry normalizes the dependency-free form."""
    out = []
    for op in ("get", "scan"):
        for rb in ("fused", "reference"):
            b = READ_DISPATCHES.get(("batches", op, rb), 0)
            d = READ_DISPATCHES.get((op, rb), 0)
            if b or d:
                labels = {"layer": "kernel", "op": op, "backend": rb}
                out.append(("read_dispatches", "counter", d, labels))
                out.append(("read_batches", "counter", b, labels))
    return out


def key_search(q, qlen, keys, klens, valid, backend: str | None = None,
               **kw):
    backend = backend or default_backend()
    if backend == "ref":
        return _ref.key_search_ref(q, qlen, keys, klens, valid)
    return _ks.key_search(q, qlen, keys, klens, valid,
                          interpret=(backend == "interpret"), **kw)


def key_search_image(q, qlen, node_img, *, keys_off, lens_off, count_off,
                     n_keys, key_words, backend: str | None = None, **kw):
    """Floor search addressed INSIDE packed node images (cfg.layout=
    "packed"): the candidate block is sliced from each request's image row
    at static layout offsets (core/schema.py) instead of arriving as
    separate key/length/valid operands."""
    backend = backend or default_backend()
    if backend == "ref":
        return _ref.key_search_image_ref(
            q, qlen, node_img, keys_off=keys_off, lens_off=lens_off,
            count_off=count_off, n_keys=n_keys, key_words=key_words)
    return _ks.key_search_image(
        q, qlen, node_img, keys_off=keys_off, lens_off=lens_off,
        count_off=count_off, n_keys=n_keys, key_words=key_words,
        interpret=(backend == "interpret"), **kw)


def leaf_merge(nitems, nlog, backptr, hints, *, node_cap, log_cap,
               backend: str | None = None, **kw):
    backend = backend or default_backend()
    if backend == "ref":
        return _ref.leaf_merge_ref(nitems, nlog, backptr, hints,
                                   node_cap=node_cap, log_cap=log_cap)
    return _lm.leaf_merge(nitems, nlog, backptr, hints, node_cap=node_cap,
                          log_cap=log_cap,
                          interpret=(backend == "interpret"), **kw)


def snapshot_delta_scatter(dst, rows, upd, backend: str | None = None, **kw):
    """Apply one delta sync's dirty rows to a resident device array
    (host->device snapshot patch).  ``dst``/``upd`` are [S, W]/[D, W] with
    trailing dims flattened; see ``repro.core.read_path.apply_snapshot_delta``
    for the whole-snapshot jnp path the store uses off-TPU."""
    backend = _store_backend(backend)
    if backend == "ref":
        return _ref.snapshot_delta_scatter_ref(dst, rows, upd)
    return _ds.snapshot_delta_scatter(dst, rows, upd,
                                      interpret=(backend == "interpret"),
                                      **kw)


def snapshot_image_scatter(image, rows, upd, backend: str | None = None,
                           **kw):
    """Apply one delta sync to the PACKED snapshot image: one contiguous
    [image_words] row DMA per dirty node (the paper's whole-node transfer,
    cfg.layout="packed").  ``image``/``upd`` are [S, IW]/[D, IW] u32; see
    ``repro.core.read_path.apply_snapshot_delta`` for the store wiring and
    the jnp oracle kept as the parity reference."""
    backend = _store_backend(backend)
    if backend == "ref":
        return _ref.snapshot_image_scatter_ref(image, rows, upd)
    return _ds.snapshot_image_scatter(image, rows, upd,
                                      interpret=(backend == "interpret"),
                                      **kw)


def log_replay_scatter(image, rows, slots, entries, *, offs,
                       backend: str | None = None, **kw):
    """Replay marshalled log entries into the resident packed snapshot
    image (the log-shipped replication feed): entry ``i`` writes its
    ~(key_words + val_words + 6) words into row ``rows[i]`` at the static
    layout offsets in ``offs`` (``core/schema.LogReplayOffsets``), instead
    of a whole ``image_words`` row DMA per dirty node.  ``slots`` are the
    per-entry log indices (monotone per row within an epoch; padding
    repeats the last record)."""
    backend = _store_backend(backend)
    if backend == "ref":
        return _ref.log_replay_scatter_ref(image, rows, slots, entries,
                                           offs=offs)
    return _ds.log_replay_scatter(image, rows, slots, entries, offs=offs,
                                  interpret=(backend == "interpret"), **kw)


def snapshot_multi_scatter(dsts, rows, upd, backend: str | None = None,
                           **kw):
    """Apply one delta sync's dirty rows to EVERY per-node field of the
    resident snapshot in a single fused kernel invocation (the paper's
    whole-node DMA).  ``dsts``/``upd`` are matching sequences of
    [S, W_f]/[D, W_f] arrays with trailing dims flattened; see
    ``repro.core.read_path.apply_snapshot_delta`` for the store wiring and
    the jnp oracle kept as the parity reference."""
    backend = _store_backend(backend)
    if backend == "ref":
        return _ref.snapshot_multi_scatter_ref(dsts, rows, upd)
    return _ds.snapshot_multi_scatter(dsts, rows, upd,
                                      interpret=(backend == "interpret"),
                                      **kw)


def batched_get_fused(snap, key, klen, *, cfg, lb_fraction: float = 0.0,
                      backend: str | None = None):
    """Fused device-resident GET: the whole batch traversal (descend, the
    key's point resolve in its leaf, version resolution) in ONE dispatch,
    the first ``cfg.cache_levels`` levels served from the snapshot's
    VMEM-pinned cache tier.  ``snap`` is a packed ``TreeSnapshot`` with
    cache fields attached.  Returns (GetResult, meters i32[3] =
    [vmem_hits, heap_gathers, lb_routed])."""
    backend = _store_backend(backend)
    if backend == "ref":
        return _ref.batched_get_fused_ref(snap, key, klen, cfg=cfg,
                                          lb_fraction=lb_fraction)
    return _fr.batched_get_fused(
        snap.image, snap.pagetable, snap.root_lid, snap.read_version,
        snap.cache_lids, snap.cache_image, key, klen, cfg=cfg,
        lb_fraction=lb_fraction, interpret=(backend == "interpret"))


def batched_scan_fused(snap, lo, lolen, hi, hilen, *, cfg,
                       lb_fraction: float = 0.0,
                       backend: str | None = None):
    """Fused device-resident SCAN — see ``batched_get_fused``.  Returns
    (ScanResult, meters i32[3])."""
    backend = _store_backend(backend)
    if backend == "ref":
        return _ref.batched_scan_fused_ref(snap, lo, lolen, hi, hilen,
                                           cfg=cfg, lb_fraction=lb_fraction)
    return _fr.batched_scan_fused(
        snap.image, snap.pagetable, snap.root_lid, snap.read_version,
        snap.cache_lids, snap.cache_image, lo, lolen, hi, hilen, cfg=cfg,
        lb_fraction=lb_fraction, interpret=(backend == "interpret"))


def paged_attention(q, k_pages, v_pages, block_tables, seq_lens,
                    start_pos=None, backend: str | None = None, **kw):
    backend = backend or default_backend()
    if backend == "ref":
        return _ref.paged_attention_ref(q, k_pages, v_pages, block_tables,
                                        seq_lens, start_pos, **kw)
    return _pa.paged_attention(q, k_pages, v_pages, block_tables, seq_lens,
                               start_pos,
                               interpret=(backend == "interpret"), **kw)
