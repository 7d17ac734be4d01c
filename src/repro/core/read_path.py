"""Batched wait-free GET/SCAN — the B-Tree accelerator (paper Section 4).

This is the pure-JAX (jit/dry-run) implementation of the interior-node search
engine (KSU ring) and the leaf-node scan engine (RSU ring).  The Pallas
kernels in ``repro.kernels`` implement the same contracts for TPU; this module
is their oracle and the path XLA:CPU can lower.

Faithfulness map:
  * request-level parallelism  -> the batch dimension B (every lane is an
    independent request; no head-of-line blocking between lanes).
  * KSU shortcut search        -> gather ONLY the shortcut block, then gather
    ONLY the selected sorted-block segment (bytes-touched matches Section 3.1).
  * wait-free MVCC reads       -> bounded old-version chain walk; a jitted
    batch executes against an immutable array snapshot, which also realizes
    the NAT guarantee (a request can never observe a half-swapped node).
  * RSU order-hint log sort    -> shift-register simulation, one vector step
    per log entry, no key comparisons (Section 4.3, Figs. 7-8).
  * merged emission            -> ranks derived from back pointers + hint
    order; equal keys come out adjacent and are resolved to the newest
    visible version (delete markers drop the key).

All shapes are static; versions are int32 on device (the paper uses 64-bit
with 5-byte log deltas; 32-bit covers any single snapshot's window and the
host keeps the authoritative 64-bit counters).

The device-resident snapshot is the PACKED node image (core/schema.py) —
one contiguous ``[S, image_words]`` u32 array holding every per-node field
at a static word offset, the reproduction's analogue of the paper's
contiguous 8 KB node buffer.  The search/scan code below reads fields
through ``snapshot_fields()``, which decodes the image via the layout's
static offsets.  The store serves reads with the fused kernels
(kernels/fused_read.py); ``batched_get``/``batched_scan`` here are the
per-level oracle the tests hold them to, and ``descend_fused`` /
``scan_from_leaf`` / ``get_from_scan`` build the fused kernels' own jnp
oracle (kernels/ref.py).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .config import HoneycombConfig
from .heap import LEAF, LOG_DELETE, NULL
from .keys import jax_key_cmp
from .schema import FIELD_NAMES, NodeImageLayout


class TreeSnapshot(NamedTuple):
    """Immutable device image of the store: ONE packed node-image array
    (every per-node field at its static layout offset — core/schema.py)
    plus the page table and the two sync scalars.

    ``cache_lids``/``cache_image`` are the device cache tier (paper
    Section 5): the root + top interior levels packed contiguously so the
    fused read kernels pin them in VMEM and resolve the first levels with
    zero heap-image gathers.  Only ``cache_lids`` travels on the sync
    feeds (~KB); ``cache_image`` is rebuilt device-side from the resident
    image via ``attach_cache_image`` wherever a snapshot is (re)staged, so
    its rows are bit-identical to the version-resolved heap rows by
    construction.  Every snapshot carries them.

    ``values`` is the device value image: one row of ``overflow_words``
    words per out-of-node value slot (core/heap.py ``OverflowHeap``), the
    value's bytes as they are.  Read batches gather their long values from
    it (``gather_values``, core/shard.py).  ``None`` while the store has
    never held a value longer than the inline width, so a store of inline
    values publishes, syncs and reads with no value program at all."""
    image: jax.Array        # u32 [S, image_words] packed node images
    pagetable: jax.Array    # i32 [LIDS]
    root_lid: jax.Array     # i32 []
    read_version: jax.Array  # i32 []
    cache_lids: jax.Array   # i32 [C], NULL-padded
    cache_image: jax.Array  # u32 [C, image_words]
    values: jax.Array | None = None       # u32 [V, overflow_words]


class SnapshotFields:
    """Per-field view of a snapshot: each attribute is a static column
    slice of the packed image decoded to the field's device dtype (bitcast
    for signed fields, so NULL = -1 survives the u32 transit); XLA folds
    the slices into the downstream gathers, so the search engines read
    exactly the bytes they always did.
    """
    __slots__ = FIELD_NAMES + ("pagetable", "root_lid", "read_version")

    def __init__(self, **fields):
        for k, v in fields.items():
            object.__setattr__(self, k, v)


def snapshot_fields(snap: TreeSnapshot,
                    cfg: HoneycombConfig) -> SnapshotFields:
    """The per-field view of a packed snapshot."""
    layout = NodeImageLayout.for_config(cfg)
    return SnapshotFields(pagetable=snap.pagetable, root_lid=snap.root_lid,
                          read_version=snap.read_version,
                          **layout.field_views(snap.image))


def attach_cache_image(snap, cfg: HoneycombConfig):
    """(Re)build the snapshot's contiguous cache tier from its own heap
    image: one version-resolved image row per cached LID, zeros in the
    NULL-padded slots.

    Called wherever a snapshot is staged — primary export, delta apply,
    follower log replay — so only the ~KB ``cache_lids`` vector ever
    travels on a feed while every serving copy's ``cache_image`` rows stay
    bit-identical to the heap rows the per-level oracle would resolve (the
    invariant the fused ≡ per-level equivalence rests on)."""
    view = snapshot_fields(snap, cfg)
    lids = snap.cache_lids
    phys = snap.pagetable[jnp.maximum(lids, 0)]
    phys = _resolve_version(view, jnp.maximum(phys, 0),
                            snap.read_version, cfg)
    rows = jnp.where((lids != NULL)[:, None], snap.image[phys],
                     jnp.uint32(0))
    return snap._replace(cache_image=rows)


class SnapshotDelta(NamedTuple):
    """One host->device sync's worth of changed state for the packed
    layout (paper Sections 3-4: node-buffer DMAs + batched page-table
    commands + read-version update).

    ``rows`` are the dirty physical slots; ``image`` carries each dirty
    node's ENTIRE packed image row — one contiguous DMA per dirty node,
    the paper's whole-node transfer unit.  Rows may repeat (padding to a
    bucketed size keeps the jit cache small); repeated rows carry
    identical data, so the scatter is idempotent.
    """
    rows: jax.Array          # i32 [D] dirty physical slots
    image: jax.Array         # u32 [D, image_words] replacement node images
    pt_lids: jax.Array       # i32 [P] page-table command targets
    pt_phys: jax.Array       # i32 [P] new mappings (may repeat, identical)
    root_lid: jax.Array      # i32 []
    read_version: jax.Array  # i32 []
    cache_lids: jax.Array    # i32 [C] next epoch's cache tier


class ValueDelta(NamedTuple):
    """One sync's new out-of-node value slots, the value image's delta:
    ``rows[i]`` holds slot ``slots[i]``.  Slots are immutable once
    written, so only slots allocated since the last sync travel.
    ``slots`` is None when ``rows`` is the whole image (the first value
    publish, or the host heap grew)."""
    slots: jax.Array | None  # i32 [D] slots written, or None
    rows: jax.Array          # u32 [D or V, overflow_words]


def apply_snapshot_delta(snap: TreeSnapshot, delta: SnapshotDelta, *,
                         cfg: HoneycombConfig, backend: str | None = None
                         ) -> TreeSnapshot:
    """Scatter one sync's dirty rows + page-table commands into a resident
    device snapshot, yielding the next snapshot.

    Functional on purpose: the input snapshot's buffers are never donated,
    so old snapshots held by in-flight batches keep answering at their read
    version (wait-free MVCC).  ONE image-row scatter patches every field of
    a dirty node in a single contiguous DMA
    (``repro.kernels.delta_scatter.snapshot_delta_scatter`` on
    ``"pallas"``/``"interpret"``; ``backend=None`` is the jnp scatter
    XLA:CPU lowers).  The delta's ``cache_lids`` replace the snapshot's
    and the contiguous cache image is rebuilt from the patched heap image
    (``attach_cache_image``) inside the same jitted apply, so every
    applied delta carries its cache tier.
    """
    if backend is None:
        image = snap.image.at[delta.rows].set(delta.image)
    else:
        from repro.kernels import ops  # deferred: kernels.ref imports us
        image = ops.snapshot_delta_scatter(snap.image, delta.rows,
                                           delta.image, backend=backend)
    return attach_cache_image(snap._replace(
        image=image,
        pagetable=snap.pagetable.at[delta.pt_lids].set(delta.pt_phys),
        root_lid=delta.root_lid, read_version=delta.read_version,
        cache_lids=delta.cache_lids), cfg)


class ScanResult(NamedTuple):
    count: jax.Array       # i32 [B] items emitted
    keys: jax.Array        # u32 [B, M, KW]
    keylens: jax.Array     # i32 [B, M]
    vals: jax.Array        # u32 [B, M, VW]
    vallens: jax.Array     # i32 [B, M]
    truncated: jax.Array   # bool [B] (ran out of result slots / leaf budget)


class GetResult(NamedTuple):
    found: jax.Array       # bool [B]
    vals: jax.Array        # u32 [B, VW]
    vallens: jax.Array     # i32 [B]


# --------------------------------------------------------------------------
# interior-node search engine (KSU)
# --------------------------------------------------------------------------

def _resolve_version(snap: SnapshotFields, phys: jax.Array, rv: jax.Array,
                     cfg: HoneycombConfig) -> jax.Array:
    """Follow old-version pointers until node version <= rv (Section 3.2).
    Bounded walk; wait-free (no locks, no retries)."""
    def step(_, p):
        too_new = (snap.version[p] > rv) & (snap.oldptr[p] != NULL)
        return jnp.where(too_new, snap.oldptr[p], p)
    return jax.lax.fori_loop(0, cfg.max_version_chain, step, phys)


def _shortcut_floor(snap: SnapshotFields, phys: jax.Array, key: jax.Array,
                    klen: jax.Array) -> jax.Array:
    """Largest shortcut index whose key <= query (0 if none: the query then
    falls below the first segment and the segment search yields -1)."""
    sck = snap.sc_keys[phys]          # [B, NSC, KW]
    scl = snap.sc_keylen[phys]        # [B, NSC]
    nsc = snap.n_shortcuts[phys]      # [B]
    c = jax_key_cmp(sck, scl, key[:, None, :], klen[:, None])
    valid = jnp.arange(sck.shape[1])[None, :] < nsc[:, None]
    leq = (c <= 0) & valid
    # last True index, 0 when none
    idx = jnp.where(leq, jnp.arange(sck.shape[1])[None, :], -1).max(axis=1)
    return jnp.maximum(idx, 0)


def _segment_floor(snap: SnapshotFields, phys: jax.Array, seg: jax.Array,
                   key: jax.Array, klen: jax.Array,
                   cfg: HoneycombConfig) -> jax.Array:
    """Floor item index within the selected segment; -1 when the query is
    below every key in the node.  Gathers ONLY the segment (bytes-touched
    parity with the paper's DMA of one segment)."""
    base = snap.sc_pos[phys, seg]                       # [B]
    offs = base[:, None] + jnp.arange(cfg.segment_items)[None, :]
    n = snap.nitems[phys]
    offs_c = jnp.minimum(offs, cfg.node_cap - 1)
    seg_keys = snap.skeys[phys[:, None], offs_c]        # [B, seg, KW]
    seg_lens = snap.skeylen[phys[:, None], offs_c]
    valid = offs < n[:, None]
    c = jax_key_cmp(seg_keys, seg_lens, key[:, None, :], klen[:, None])
    leq = (c <= 0) & valid
    local = jnp.where(leq, jnp.arange(cfg.segment_items)[None, :], -1).max(axis=1)
    return jnp.where(local >= 0, base + local, -1)


def descend(snap: SnapshotFields, key: jax.Array, klen: jax.Array,
            cfg: HoneycombConfig) -> jax.Array:
    """Traverse interior nodes root->leaf for a batch, over a field view
    (``snapshot_fields``).  Returns the resolved physical slot of the leaf
    each request lands in."""
    B = key.shape[0]
    rv = snap.read_version
    lid = jnp.broadcast_to(snap.root_lid, (B,))

    def level(_, state):
        lid, phys, done = state
        cur = _resolve_version(snap, snap.pagetable[lid], rv, cfg)
        cur = jnp.where(done, phys, cur)
        is_leaf = snap.ntype[cur] == LEAF
        seg = _shortcut_floor(snap, cur, key, klen)
        idx = _segment_floor(snap, cur, seg, key, klen, cfg)
        child = jnp.where(idx >= 0,
                          snap.svals[cur, jnp.maximum(idx, 0), 0].astype(jnp.int32),
                          snap.left_child[cur])
        new_done = done | is_leaf
        new_lid = jnp.where(new_done, lid, child)
        return new_lid, jnp.where(done, phys, cur), new_done

    _, phys, _ = jax.lax.fori_loop(
        0, cfg.max_height,
        level, (lid, jnp.zeros((B,), jnp.int32), jnp.zeros((B,), bool)))
    return phys


def fused_view(snap: TreeSnapshot, cfg: HoneycombConfig) -> SnapshotFields:
    """Field view over the heap image CONCATENATED with the snapshot's
    cache image: combined row indices >= S address cache rows.  Because
    cache rows are bit-identical to their version-resolved heap rows
    (``attach_cache_image``), any search code running on this view yields
    the same results whether a level resolved from the cache or the heap —
    THE structural argument behind fused ≡ per-level."""
    layout = NodeImageLayout.for_config(cfg)
    combined = jnp.concatenate([snap.image, snap.cache_image], axis=0)
    return SnapshotFields(pagetable=snap.pagetable, root_lid=snap.root_lid,
                          read_version=snap.read_version,
                          **layout.field_views(combined))


def lb_routed_lanes(lane: jax.Array, lb_fraction: float) -> jax.Array:
    """Deterministic Section-5 dual-pipe routing: lanes whose index mod 16
    falls under round(lb_fraction * 16) send their cache-hit lookups down
    the heap pipe anyway.  Compile-time constant per lb_fraction, identical
    between the jnp oracle (lane = arange over the batch) and the Pallas
    kernels (lane = program id), so routing never perturbs results."""
    return (lane % 16) < int(round(lb_fraction * 16))


def descend_fused(snap: TreeSnapshot, view: SnapshotFields, key: jax.Array,
                  klen: jax.Array, cfg: HoneycombConfig, *,
                  lb_fraction: float = 0.0):
    """Cache-tiered descend (the fused path's oracle): a level whose LID is
    in the snapshot's cache tier resolves straight to its cache row
    (combined index S + slot — no pagetable lookup, no MVCC walk, zero heap
    gathers), everything below the cached frontier falls through to the
    heap path, and an ``lb_fraction`` slice of cache-HIT lanes is routed to
    the heap pipe anyway (Section 5's load balancer: identical results,
    different byte split).  ``view`` must be ``fused_view(snap, cfg)``.

    Returns (leaf phys in the combined view, meters i32[3] =
    [vmem_hits, heap_gathers, lb_routed] counted over traversed levels).
    """
    S = snap.image.shape[0]
    clids = snap.cache_lids
    B = key.shape[0]
    rv = view.read_version
    lid = jnp.broadcast_to(view.root_lid, (B,))
    routed_lane = lb_routed_lanes(jnp.arange(B), lb_fraction)

    def level(_, state):
        lid, phys, done, vh, hg, lr = state
        eq = clids[None, :] == lid[:, None]
        hit = eq.any(axis=1) & (lid != NULL)
        slot = jnp.argmax(eq, axis=1).astype(jnp.int32)
        use_cache = hit & ~routed_lane
        heap_phys = _resolve_version(view, view.pagetable[lid], rv, cfg)
        cur = jnp.where(use_cache, S + slot, heap_phys)
        cur = jnp.where(done, phys, cur)
        live = ~done
        vh = vh + (use_cache & live).sum(dtype=jnp.int32)
        hg = hg + (~use_cache & live).sum(dtype=jnp.int32)
        lr = lr + (hit & routed_lane & live).sum(dtype=jnp.int32)
        is_leaf = view.ntype[cur] == LEAF
        seg = _shortcut_floor(view, cur, key, klen)
        idx = _segment_floor(view, cur, seg, key, klen, cfg)
        child = jnp.where(idx >= 0,
                          view.svals[cur, jnp.maximum(idx, 0), 0]
                          .astype(jnp.int32),
                          view.left_child[cur])
        new_done = done | is_leaf
        new_lid = jnp.where(new_done, lid, child)
        return (new_lid, jnp.where(done, phys, cur), new_done, vh, hg, lr)

    z = jnp.zeros((), jnp.int32)
    _, phys, _, vh, hg, lr = jax.lax.fori_loop(
        0, cfg.max_height, level,
        (lid, jnp.zeros((B,), jnp.int32), jnp.zeros((B,), bool), z, z, z))
    return phys, jnp.stack([vh, hg, lr])


# --------------------------------------------------------------------------
# leaf-node scan engine (RSU)
# --------------------------------------------------------------------------

def log_sort_positions(hints: jax.Array, nlog: jax.Array,
                       log_cap: int) -> jax.Array:
    """Shift-register sort of the log block using order hints (Fig. 8).

    hints: i32 [B, L]; returns pos [B, L] — the position of each log entry in
    ascending key order.  One vector step per entry, no key comparisons,
    mirroring the paper's one-cycle-per-item hardware sort.
    """
    B, L = hints.shape

    def insert(j, pos):
        # entries already placed at positions >= hints[:, j] shift right
        placed = jnp.arange(L)[None, :] < j
        active = placed & (j < nlog)[:, None]
        shift = active & (pos >= hints[:, j][:, None])
        pos = pos + shift.astype(pos.dtype)
        return pos.at[:, j].set(jnp.where(j < nlog, hints[:, j], pos[:, j]))

    del log_cap  # L is static from the shape
    pos0 = jnp.zeros((B, L), hints.dtype)
    return jax.lax.fori_loop(0, L, insert, pos0)


def _resolve_leaf(snap: SnapshotFields, phys: jax.Array,
                  cfg: HoneycombConfig):
    """Merged, shadow-resolved enumeration of one leaf per request.

    Returns (keys [B,T,KW], keylens, vals [B,T,VW], vallens, live [B,T]) in
    ascending key order, where T = node_cap + log_cap.  ``live`` marks items
    that survive MVCC filtering and delete markers.
    """
    c = cfg
    N, L = c.node_cap, c.log_cap
    T = N + L
    rv = snap.read_version
    nv = snap.version[phys]                    # [B]
    nit = snap.nitems[phys]
    nlg = snap.nlog[phys]

    # --- RSU log sort via order hints -------------------------------------
    hints = snap.log_hint[phys].astype(jnp.int32)          # [B, L]
    logpos = log_sort_positions(hints, nlg, L)             # [B, L]

    # merged rank: log entries go right before the sorted item their back
    # pointer names; hint order breaks ties among them (Section 4.3)
    rank_log = snap.log_backptr[phys] * (L + 1) + logpos   # [B, L]
    rank_sorted = jnp.arange(N)[None, :] * (L + 1) + L     # [1, N]

    svis = jnp.arange(N)[None, :] < nit[:, None]
    lvis_slot = jnp.arange(L)[None, :] < nlg[:, None]
    lver = nv[:, None] + snap.log_vdelta[phys]
    lvis = lvis_slot & (lver <= rv)

    keys = jnp.concatenate([snap.skeys[phys], snap.log_keys[phys]], axis=1)
    klens = jnp.concatenate([snap.skeylen[phys], snap.log_keylen[phys]], axis=1)
    vals = jnp.concatenate([snap.svals[phys], snap.log_vals[phys]], axis=1)
    vlens = jnp.concatenate([snap.svallen[phys], snap.log_vallen[phys]], axis=1)
    vers = jnp.concatenate(
        [jnp.broadcast_to(nv[:, None], (nv.shape[0], N)), lver], axis=1)
    isdel = jnp.concatenate(
        [jnp.zeros((nv.shape[0], N), bool),
         snap.log_op[phys] == LOG_DELETE], axis=1)
    vis = jnp.concatenate([svis, lvis], axis=1)
    slot_used = jnp.concatenate([svis, lvis_slot], axis=1)
    rank = jnp.concatenate(
        [jnp.broadcast_to(rank_sorted, (nv.shape[0], N)), rank_log], axis=1)
    rank = jnp.where(slot_used, rank, jnp.iinfo(jnp.int32).max)

    # order by rank (stable, ranks of used slots are unique)
    order = jnp.argsort(rank, axis=1)
    take = lambda a: jnp.take_along_axis(
        a, order.reshape(order.shape + (1,) * (a.ndim - 2)), axis=1)
    keys, klens = take(keys), jnp.take_along_axis(klens, order, axis=1)
    vals, vlens = take(vals), jnp.take_along_axis(vlens, order, axis=1)
    vers = jnp.take_along_axis(vers, order, axis=1)
    isdel = jnp.take_along_axis(isdel, order, axis=1)
    vis = jnp.take_along_axis(vis, order, axis=1)
    used = jnp.take_along_axis(slot_used, order, axis=1)

    # --- shadow resolution: equal keys are adjacent; newest visible wins ---
    same_prev = (jax_key_cmp(keys[:, 1:], klens[:, 1:],
                             keys[:, :-1], klens[:, :-1]) == 0) \
        & used[:, 1:] & used[:, :-1]
    run_id = jnp.concatenate(
        [jnp.zeros((keys.shape[0], 1), jnp.int32),
         jnp.cumsum(~same_prev, axis=1).astype(jnp.int32)], axis=1)
    vmask = jnp.where(vis, vers, jnp.iinfo(jnp.int32).min)
    # per-run max version via scatter-max into T bins (run_id < T)
    seg_max = jnp.full((keys.shape[0], T), jnp.iinfo(jnp.int32).min,
                       jnp.int32)
    seg_max = seg_max.at[jnp.arange(keys.shape[0])[:, None], run_id].max(vmask)
    winner = vis & (vmask == seg_max[jnp.arange(keys.shape[0])[:, None],
                                     run_id])
    live = winner & ~isdel
    return keys, klens, vals, vlens, live


def batched_scan(snap: TreeSnapshot, lo: jax.Array, lolen: jax.Array,
                 hi: jax.Array, hilen: jax.Array,
                 cfg: HoneycombConfig) -> ScanResult:
    """SCAN(K_l, K_u) for a batch: floor-start semantics, forward across
    sibling leaves with bounded budget (Section 3.3), level by level — the
    per-level oracle of the fused SCAN."""
    view = snapshot_fields(snap, cfg)
    leaf0 = descend(view, lo, lolen, cfg)
    return scan_from_leaf(view, leaf0, lo, lolen, hi, hilen, cfg)


def scan_from_leaf(snap: SnapshotFields, leaf0: jax.Array,
                   lo: jax.Array, lolen: jax.Array,
                   hi: jax.Array, hilen: jax.Array,
                   cfg: HoneycombConfig) -> ScanResult:
    """The scan engine proper, starting from pre-descended leaf slots —
    shared verbatim between the per-level oracle (heap-view ``snap``, heap
    ``leaf0``) and the fused oracle (combined cache+heap view,
    ``descend_fused`` leaf slots), so the two paths cannot drift."""
    c = cfg
    B = lo.shape[0]
    M = c.max_scan_items
    KW, VW = c.key_words, c.val_words
    T = c.node_cap + c.log_cap
    rv = snap.read_version

    out_keys = jnp.zeros((B, M, KW), jnp.uint32)
    out_klens = jnp.zeros((B, M), jnp.int32)
    out_vals = jnp.zeros((B, M, VW), jnp.uint32)
    out_vlens = jnp.zeros((B, M), jnp.int32)
    count = jnp.zeros((B,), jnp.int32)
    trunc = jnp.zeros((B,), bool)
    rows = jnp.arange(B)

    # ---- floor pre-pass: walk left until some visible key <= lo ----------
    def floor_step(_, state):
        phys, fkeys, fklens, fvals, fvlens, have = state
        keys, klens, vals, vlens, live = _resolve_leaf(snap, phys, c)
        leq = live & (jax_key_cmp(keys, klens, lo[:, None, :],
                                  lolen[:, None]) <= 0)
        idx = jnp.where(leq, jnp.arange(T)[None, :], -1).max(axis=1)
        found = idx >= 0
        sel = jnp.maximum(idx, 0)
        upd = found & ~have
        fkeys = jnp.where(upd[:, None], keys[rows, sel], fkeys)
        fklens = jnp.where(upd, klens[rows, sel], fklens)
        fvals = jnp.where(upd[:, None], vals[rows, sel], fvals)
        fvlens = jnp.where(upd, vlens[rows, sel], fvlens)
        have = have | found
        nxt = snap.lsib[phys]
        can_move = ~have & (nxt != NULL)
        nxt_phys = _resolve_version(
            snap, snap.pagetable[jnp.maximum(nxt, 0)], rv, c)
        phys = jnp.where(can_move, nxt_phys, phys)
        return phys, fkeys, fklens, fvals, fvlens, have

    _, fkeys, fklens, fvals, fvlens, have_floor = jax.lax.fori_loop(
        0, c.max_scan_leaves, floor_step,
        (leaf0, jnp.zeros((B, KW), jnp.uint32), jnp.zeros((B,), jnp.int32),
         jnp.zeros((B, VW), jnp.uint32), jnp.zeros((B,), jnp.int32),
         jnp.zeros((B,), bool)))

    emit_floor = have_floor & (jax_key_cmp(fkeys, fklens, hi, hilen) <= 0)
    out_keys = out_keys.at[:, 0].set(jnp.where(emit_floor[:, None], fkeys, 0))
    out_klens = out_klens.at[:, 0].set(jnp.where(emit_floor, fklens, 0))
    out_vals = out_vals.at[:, 0].set(jnp.where(emit_floor[:, None], fvals, 0))
    out_vlens = out_vlens.at[:, 0].set(jnp.where(emit_floor, fvlens, 0))
    count = count + emit_floor.astype(jnp.int32)

    # ---- forward scan across sibling leaves ------------------------------
    def leaf_step(_, state):
        (phys, out_keys, out_klens, out_vals, out_vlens, count, trunc,
         done) = state
        keys, klens, vals, vlens, live = _resolve_leaf(snap, phys, c)
        gt_lo = jax_key_cmp(keys, klens, lo[:, None, :], lolen[:, None]) > 0
        leq_hi = jax_key_cmp(keys, klens, hi[:, None, :], hilen[:, None]) <= 0
        emit = live & gt_lo & leq_hi & ~done[:, None]
        local = jnp.cumsum(emit, axis=1) - 1
        slot = count[:, None] + local
        ok = emit & (slot < M)
        # non-emitted lanes target the out-of-range slot M and are dropped,
        # so emitted slots are written exactly once (scatter stays ordered)
        slot_c = jnp.where(ok, jnp.clip(slot, 0, M - 1), M)
        br = rows[:, None]
        out_keys = out_keys.at[br, slot_c].set(keys, mode="drop")
        out_klens = out_klens.at[br, slot_c].set(klens, mode="drop")
        out_vals = out_vals.at[br, slot_c].set(vals, mode="drop")
        out_vlens = out_vlens.at[br, slot_c].set(vlens, mode="drop")
        count = count + ok.sum(axis=1)
        trunc = trunc | (emit & ~ok).any(axis=1)
        # a request is done when this leaf contained a live key beyond hi or
        # there is no right sibling
        past_hi = (live & ~leq_hi).any(axis=1)
        nxt = snap.rsib[phys]
        done = done | past_hi | (nxt == NULL) | trunc
        nxt_phys = _resolve_version(
            snap, snap.pagetable[jnp.maximum(nxt, 0)], rv, c)
        phys = jnp.where(done, phys, nxt_phys)
        return (phys, out_keys, out_klens, out_vals, out_vlens, count,
                trunc, done)

    state = (leaf0, out_keys, out_klens, out_vals, out_vlens, count, trunc,
             jnp.zeros((B,), bool))
    (_, out_keys, out_klens, out_vals, out_vlens, count, trunc,
     done) = jax.lax.fori_loop(0, c.max_scan_leaves, leaf_step, state)
    trunc = trunc | ~done
    return ScanResult(count, out_keys, out_klens, out_vals, out_vlens, trunc)


def batched_get(snap: TreeSnapshot, key: jax.Array, klen: jax.Array,
                cfg: HoneycombConfig) -> GetResult:
    """GET(K) implemented as SCAN(K, K) + post-processing (Section 3.3) —
    the per-level oracle of the fused GET."""
    res = batched_scan(snap, key, klen, key, klen, cfg)
    return get_from_scan(res, key, klen)


def get_from_scan(res: ScanResult, key: jax.Array,
                  klen: jax.Array) -> GetResult:
    """The GET equality post-pass over a SCAN(K, K) result (shared with the
    fused oracle).  A key not found answers zero value words and length,
    not the floor item the scan emitted below it."""
    eq = (jax_key_cmp(res.keys, res.keylens, key[:, None, :],
                      klen[:, None]) == 0) \
        & (jnp.arange(res.keys.shape[1])[None, :] < res.count[:, None])
    found = eq.any(axis=1)
    idx = jnp.argmax(eq, axis=1)
    rows = jnp.arange(key.shape[0])
    return GetResult(found,
                     jnp.where(found[:, None], res.vals[rows, idx], 0),
                     jnp.where(found, res.vallens[rows, idx], 0))
