"""Distributed paged decode attention: shard_map-local page pools.

The baseline decode path gathers KV pages through XLA's global-gather
semantics: with pools sharded over (data x model) and block tables holding
global page ids, GSPMD cannot prove locality, so it all-gathers the pools
(collective-bound) and replicates the attention math on the model axis
(compute/memory waste).  Every decode cell in the baseline roofline table
is collective-dominated because of this.

This module is the beyond-paper optimization (EXPERIMENTS.md §Perf): the
same data-locality insight Honeycomb applies across PCIe — *place the data
so the fast path never crosses the slow link* — applied to ICI.  Pages are
placed in the pool shard that owns the sequence (the serving engine's
allocator is per-host anyway), and the gather + attention run inside a
``shard_map`` where every reference is provably local:

  * batch and pool page-dim shard together on ("pod","data") — a sequence's
    pages live with its lanes; block-table ids are rebased to local rows;
  * the model axis shards KV heads when divisible (q heads follow; zero
    collectives), else head_dim (one [B,KVH,G,S] logits psum per step);
  * the new token's KV scatter happens on the owning shard only.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

F32 = jnp.float32
NEG_INF = -1e30


def paged_attention_local(q, k_pages, v_pages, block_tables, seq_lens,
                          start_pos, k_new, v_new, *, mesh: Mesh,
                          batch_axes, kv_head_axis: str | None,
                          head_dim_axis: str | None, page_size: int,
                          scale: float, softcap: float = 0.0):
    """Locality-preserving paged decode attention + KV scatter.

    q:            [B, H, D]
    k/v_pages:    [NP, P, KVH, D] — NP sharded on ``batch_axes`` aligned
                  with B (sequence i's pages live in shard i's rows)
    block_tables: [B, PPS] GLOBAL page ids (engine layout: shard-contiguous)
    seq_lens:     [B] history length (the new token's position)
    k_new/v_new:  [B, KVH, D] this step's KV (scattered locally)
    returns (out [B, H, D] f32, k_pages, v_pages)
    """
    B, H, D = q.shape
    NP = k_pages.shape[0]
    KVH = k_pages.shape[2]
    G = H // KVH
    qg = q.reshape(B, KVH, G, D)

    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_data = 1
    for a in batch_axes:
        n_data *= sizes[a]
    np_local = NP // n_data

    kv_spec = P(batch_axes, None, kv_head_axis, head_dim_axis)
    q_spec = P(batch_axes, kv_head_axis, None, head_dim_axis)
    new_spec = P(batch_axes, kv_head_axis, head_dim_axis)
    out_spec = P(batch_axes, kv_head_axis, None, head_dim_axis)

    def body(qg, kp, vp, bt, lens, start, kn, vn):
        # rebase global page ids to this shard's local pool rows
        shard = jnp.int32(0)
        for a in batch_axes:
            shard = shard * sizes[a] + jax.lax.axis_index(a)
        bt_loc = bt - shard * np_local
        rows = jnp.arange(bt.shape[0])
        page = bt_loc[rows, lens // page_size]
        slot = lens % page_size
        kp = kp.at[page, slot].set(kn.astype(kp.dtype))
        vp = vp.at[page, slot].set(vn.astype(vp.dtype))
        new_lens = lens + 1

        k = kp[bt_loc].reshape(bt.shape[0], -1, kp.shape[2], kp.shape[3])
        v = vp[bt_loc].reshape(bt.shape[0], -1, vp.shape[2], vp.shape[3])
        s = jnp.einsum("bkgd,bskd->bkgs", qg.astype(F32) * scale,
                       k.astype(F32))
        if head_dim_axis is not None:
            # contraction dim was sharded: finish the dot before softmax
            s = jax.lax.psum(s, head_dim_axis)
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        pos = jnp.arange(k.shape[1])[None, :]
        mask = (pos < new_lens[:, None]) & (pos >= start[:, None])
        s = jnp.where(mask[:, None, None, :], s, NEG_INF)
        probs = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgs,bskd->bkgd", probs, v.astype(F32))
        return o, kp, vp

    out, kp, vp = shard_map(
        body, mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec, P(batch_axes, None),
                  P(batch_axes), P(batch_axes), new_spec, new_spec),
        out_specs=(out_spec, kv_spec, kv_spec),
        check_vma=False,
    )(qg, k_pages, v_pages, block_tables, seq_lens, start_pos, k_new, v_new)
    return out.reshape(B, H, D), kp, vp
