"""Pure-jnp oracles for every Pallas kernel (the reference the shape/dtype
sweeps assert against)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import read_path as _rp
from repro.core.keys import jax_key_cmp
from repro.core.read_path import log_sort_positions


def key_search_ref(q, qlen, keys, klens, valid):
    """Floor search oracle: largest valid index with key <= query, else -1."""
    c = jax_key_cmp(keys, klens, q[:, None, :], qlen[:, None])
    leq = (c <= 0) & (valid != 0)
    n = keys.shape[1]
    return jnp.where(leq, jnp.arange(n)[None, :], -1).max(axis=1) \
        .astype(jnp.int32)


def key_search_image_ref(q, qlen, img, *, keys_off: int, lens_off: int,
                         count_off: int, n_keys: int, key_words: int):
    """Floor search over packed node images: decode the candidate block
    from each request's image row at the static layout offsets, then the
    plain floor-search oracle."""
    B = img.shape[0]
    keys = img[:, keys_off:keys_off + n_keys * key_words] \
        .reshape(B, n_keys, key_words)
    klens = img[:, lens_off:lens_off + n_keys].astype(jnp.int32)
    count = img[:, count_off].astype(jnp.int32)
    valid = (jnp.arange(n_keys)[None, :] < count[:, None]).astype(jnp.int32)
    return key_search_ref(q, qlen, keys, klens, valid)


def leaf_merge_ref(nitems, nlog, backptr, hints, *, node_cap: int,
                   log_cap: int):
    """Merged-emission permutation oracle (rank sort via argsort)."""
    B = nitems.shape[0]
    N, L = node_cap, log_cap
    T = N + L
    logpos = log_sort_positions(hints.astype(jnp.int32), nlog, L)
    rank_log = backptr * (L + 1) + logpos
    rank_sorted = jnp.arange(N)[None, :] * (L + 1) + L
    svalid = jnp.arange(N)[None, :] < nitems[:, None]
    lvalid = jnp.arange(L)[None, :] < nlog[:, None]
    imax = jnp.iinfo(jnp.int32).max
    rank = jnp.concatenate([
        jnp.where(svalid, rank_sorted, imax),
        jnp.where(lvalid, rank_log, imax)], axis=1)
    perm = jnp.argsort(rank, axis=1, stable=True).astype(jnp.int32)
    valid = jnp.concatenate([svalid, lvalid], axis=1).astype(jnp.int32)
    return perm, valid


def snapshot_delta_scatter_ref(dst, rows, upd):
    """Delta-sync image-row scatter oracle: dst[rows[i]] = upd[i], one
    whole node image per dirty row.

    Duplicate rows must carry identical data (the store pads deltas with
    repeats), so application order is immaterial."""
    return dst.at[rows].set(upd)


def log_replay_scatter_ref(image, rows, slots, entries, *, offs):
    """Log-replay scatter oracle: apply one epoch's marshalled log entries
    to a packed node image (the log-shipped replication feed).

    Entry ``i`` writes its key/value lanes, lengths, op code, backptr,
    hint and vdelta words into image row ``rows[i]`` at the static layout
    offsets in ``offs`` (a ``schema.LogReplayOffsets``), each per-slot
    field advanced by ``slots[i] * width``; ``nlog`` becomes each touched
    row's highest ``slots + 1`` (log appends are monotone per row within
    an epoch — the kernel's last in-order write — and padded duplicate
    entries repeat the same record, so order is immaterial)."""
    kw, vw = offs.key_words, offs.val_words
    S, IW = image.shape
    rows = rows.astype(jnp.int32)
    j = slots.astype(jnp.int32)
    flat = image.reshape(-1)
    base = rows * IW

    def col(off):                     # flat index of a width-1 slot field
        return base + off + j

    flat = flat.at[(base[:, None] + offs.log_keys + j[:, None] * kw
                    + jnp.arange(kw)[None, :]).reshape(-1)] \
        .set(entries[:, 0:kw].reshape(-1))
    flat = flat.at[col(offs.log_keylen)].set(entries[:, kw])
    flat = flat.at[(base[:, None] + offs.log_vals + j[:, None] * vw
                    + jnp.arange(vw)[None, :]).reshape(-1)] \
        .set(entries[:, kw + 1:kw + 1 + vw].reshape(-1))
    flat = flat.at[col(offs.log_vallen)].set(entries[:, kw + 1 + vw])
    flat = flat.at[col(offs.log_op)].set(entries[:, kw + vw + 2])
    flat = flat.at[col(offs.log_backptr)].set(entries[:, kw + vw + 3])
    flat = flat.at[col(offs.log_hint)].set(entries[:, kw + vw + 4])
    flat = flat.at[col(offs.log_vdelta)].set(entries[:, kw + vw + 5])
    img = flat.reshape(S, IW)
    # per-row final count: entries sharing a row all carry that row's max
    # slots+1, so the duplicate-index set below is order-free
    same_row = rows[:, None] == rows[None, :]
    final_nlog = jnp.where(same_row, (j + 1)[None, :], 0).max(axis=1)
    return img.at[rows, offs.nlog].set(final_nlog.astype(image.dtype))


def batched_scan_fused_ref(snap, lo, lolen, hi, hilen, *, cfg,
                           lb_fraction: float = 0.0):
    """Fused SCAN oracle: the whole traversal — cache-tiered descend, leaf
    resolve, log merge, version resolution — as ONE jnp expression over the
    snapshot's combined cache+heap image view.  Returns
    (ScanResult, meters i32[3] = [vmem_hits, heap_gathers, lb_routed]).

    Descend levels whose LID sits in the snapshot's cache tier resolve from
    the cache rows (no pagetable/MVCC walk); the scan engine itself is the
    reference implementation running on the combined view, so results are
    bit-identical to ``read_path.batched_scan`` by construction."""
    view = _rp.fused_view(snap, cfg)
    leaf0, meters = _rp.descend_fused(snap, view, lo, lolen, cfg,
                                      lb_fraction=lb_fraction)
    res = _rp.scan_from_leaf(view, leaf0, lo, lolen, hi, hilen, cfg)
    return res, meters


def batched_get_fused_ref(snap, key, klen, *, cfg,
                          lb_fraction: float = 0.0):
    """Fused GET oracle: fused SCAN(K, K) + the shared equality post-pass.
    Returns (GetResult, meters i32[3])."""
    res, meters = batched_scan_fused_ref(snap, key, klen, key, klen,
                                         cfg=cfg, lb_fraction=lb_fraction)
    return _rp.get_from_scan(res, key, klen), meters


def paged_attention_ref(q, k_pages, v_pages, block_tables, seq_lens,
                        start_pos=None, *, scale: float | None = None,
                        softcap: float = 0.0):
    """Gather-then-dense-attention oracle."""
    B, H, D = q.shape
    _, P, KVH, _ = k_pages.shape
    G = H // KVH
    PPS = block_tables.shape[1]
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    if start_pos is None:
        start_pos = jnp.zeros_like(seq_lens)
    k = k_pages[block_tables].reshape(B, PPS * P, KVH, D)
    v = v_pages[block_tables].reshape(B, PPS * P, KVH, D)
    qg = q.reshape(B, KVH, G, D).astype(jnp.float32)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k.astype(jnp.float32)) * scale
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    pos = jnp.arange(PPS * P)[None, :]
    mask = (pos < seq_lens[:, None]) & (pos >= start_pos[:, None])
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p, v.astype(jnp.float32))
    return o.reshape(B, H, D).astype(q.dtype)
