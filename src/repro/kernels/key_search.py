"""KSU: key search unit as a Pallas TPU kernel (paper Section 4.2, Fig. 6).

Floor search — largest key <= query — over a block of candidate keys per
request.  This one primitive implements both stages of the paper's interior
search: the shortcut-block search and the sorted-segment search (and the
leaf floor probe), exactly as the hardware KSU is reused across block types.

Hardware adaptation: the FPGA KSU streams variable-size keys through a
16-byte compare pipeline fed by barrel shifters.  The TPU-native equivalent
packs keys big-endian in uint32 lanes; a whole [block, n_keys] tile of
comparisons is one VPU op: compare all lanes, select the first differing
lane, tie-break on length.  The reduction to the floor index is a masked
max over key positions.

VMEM budget per grid step (defaults B_BLK=128, N=64, KW=8):
  queries 128*8*4 B = 4 KiB, keys 128*64*8*4 B = 1 MiB, lens 32 KiB
  => comfortably inside the ~16 MiB VMEM of a TPU core; B_BLK and the key
  block are the tunable BlockSpec knobs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEFAULT_BLOCK_B = 128


def _cmp_leq(keys, klens, q, qlen):
    """sign(memcmp(keys, q)) <= 0 elementwise over [B, N] candidates."""
    neq = keys != q[:, None, :]
    any_neq = neq.any(axis=-1)
    first = jnp.argmax(neq, axis=-1)
    kv = jnp.take_along_axis(keys, first[..., None], axis=-1)[..., 0]
    qv = jnp.take_along_axis(
        jnp.broadcast_to(q[:, None, :], keys.shape), first[..., None],
        axis=-1)[..., 0]
    lane_lt = kv < qv
    len_leq = klens <= qlen[:, None]
    return jnp.where(any_neq, lane_lt, len_leq)


def _key_search_kernel(q_ref, qlen_ref, keys_ref, klens_ref, valid_ref,
                       out_ref):
    """One grid step: floor index for a block of requests."""
    q = q_ref[...]                 # [B_blk, KW] uint32
    qlen = qlen_ref[...]           # [B_blk]
    keys = keys_ref[...]           # [B_blk, N, KW] uint32
    klens = klens_ref[...]         # [B_blk, N]
    valid = valid_ref[...] != 0    # [B_blk, N]

    leq = _cmp_leq(keys, klens, q, qlen) & valid
    n = keys.shape[1]
    idx = jnp.where(leq, jax.lax.broadcasted_iota(jnp.int32, leq.shape, 1),
                    -1).max(axis=1)
    out_ref[...] = idx.astype(jnp.int32)


def _key_search_image_kernel(q_ref, qlen_ref, img_ref, out_ref, *,
                             keys_off: int, lens_off: int, count_off: int,
                             n_keys: int, key_words: int):
    """Floor search straight off PACKED node images: the candidate block
    (keys, lengths, live count) is sliced out of each request's
    [image_words] u32 row at STATIC layout offsets (core/schema.py) — the
    kernel walks the image, no host-side per-field gather feeds it."""
    q = q_ref[...]                 # [B_blk, KW] uint32
    qlen = qlen_ref[...]           # [B_blk]
    img = img_ref[...]             # [B_blk, IW] uint32 packed node images
    B = img.shape[0]
    keys = img[:, keys_off:keys_off + n_keys * key_words] \
        .reshape(B, n_keys, key_words)
    klens = img[:, lens_off:lens_off + n_keys].astype(jnp.int32)
    count = img[:, count_off].astype(jnp.int32)
    valid = jax.lax.broadcasted_iota(jnp.int32, (B, n_keys), 1) \
        < count[:, None]
    leq = _cmp_leq(keys, klens, q, qlen) & valid
    idx = jnp.where(leq, jax.lax.broadcasted_iota(jnp.int32, leq.shape, 1),
                    -1).max(axis=1)
    out_ref[...] = idx.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=(
    "keys_off", "lens_off", "count_off", "n_keys", "key_words", "block_b",
    "interpret"))
def key_search_image(q, qlen, node_img, *, keys_off: int, lens_off: int,
                     count_off: int, n_keys: int, key_words: int,
                     block_b: int = DEFAULT_BLOCK_B,
                     interpret: bool = False):
    """Floor search over a candidate block addressed INSIDE packed node
    images (e.g. the shortcut block at the layout's
    sc_keys/sc_keylen/n_shortcuts offsets).

    q:        [B, KW] uint32 packed big-endian query keys
    qlen:     [B]     int32 byte lengths
    node_img: [B, IW] uint32 — one packed image row per request (the node
              each request is searching, gathered by physical slot)
    keys_off/lens_off/count_off: word offsets of the candidate keys, key
              lengths and live-candidate count within the image row
    n_keys/key_words: candidate block geometry (static)
    returns [B] int32 floor indices, -1 when no candidate <= query.
    """
    B, IW = node_img.shape
    if B % block_b != 0:
        pad = -B % block_b
        q = jnp.pad(q, ((0, pad), (0, 0)))
        qlen = jnp.pad(qlen, (0, pad))
        node_img = jnp.pad(node_img, ((0, pad), (0, 0)))
    Bp = q.shape[0]
    kern = functools.partial(
        _key_search_image_kernel, keys_off=keys_off, lens_off=lens_off,
        count_off=count_off, n_keys=n_keys, key_words=key_words)
    out = pl.pallas_call(
        kern,
        grid=(Bp // block_b,),
        in_specs=[
            pl.BlockSpec((block_b, q.shape[1]), lambda i: (i, 0)),
            pl.BlockSpec((block_b,), lambda i: (i,)),
            pl.BlockSpec((block_b, IW), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_b,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((Bp,), jnp.int32),
        interpret=interpret,
    )(q, qlen, node_img)
    return out[:B]


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def key_search(q, qlen, keys, klens, valid, *, block_b: int = DEFAULT_BLOCK_B,
               interpret: bool = False):
    """Floor search: largest index i with valid[b,i] and keys[b,i] <= q[b].

    q:     [B, KW] uint32 packed big-endian query keys
    qlen:  [B]     int32 byte lengths
    keys:  [B, N, KW] uint32 candidate keys (shortcut block or segment)
    klens: [B, N]  int32
    valid: [B, N]  int32 (0/1)
    returns [B] int32 floor indices, -1 when no candidate <= query.
    """
    B, N, KW = keys.shape
    if B % block_b != 0:
        pad = -B % block_b
        q = jnp.pad(q, ((0, pad), (0, 0)))
        qlen = jnp.pad(qlen, (0, pad))
        keys = jnp.pad(keys, ((0, pad), (0, 0), (0, 0)))
        klens = jnp.pad(klens, ((0, pad), (0, 0)))
        valid = jnp.pad(valid, ((0, pad), (0, 0)))
    Bp = q.shape[0]
    grid = (Bp // block_b,)
    out = pl.pallas_call(
        _key_search_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, KW), lambda i: (i, 0)),
            pl.BlockSpec((block_b,), lambda i: (i,)),
            pl.BlockSpec((block_b, N, KW), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_b, N), lambda i: (i, 0)),
            pl.BlockSpec((block_b, N), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_b,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((Bp,), jnp.int32),
        interpret=interpret,
    )(q, qlen, keys, klens, valid)
    return out[:B]
