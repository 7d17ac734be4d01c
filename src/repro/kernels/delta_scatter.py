"""Delta-sync row scatter as a Pallas TPU kernel (host->device snapshot
delta application, paper Sections 3-4).

One sync's dirty node rows arrive as a dense [D, W] update block plus a
prefetched [D] row-index vector; the kernel writes each update row over
the matching row of the resident [S, W] device array in place.  This is the
device half of the PCIe analogue: the host transfers O(dirty) bytes and the
on-device image is patched, never rebuilt.

TPU addressing: HBM arrays are tiled 8 rows x 128 lanes, and Mosaic only
DMAs windows aligned to that tile — a single row of an [S, W] array is not
addressable on its own.  Each scatter step therefore reads the 8-row
window holding its target row into on-core memory, replaces the row there,
and writes the window back (a read-modify-write of one tile row group).
The grid runs the steps in order, so repeated rows and rows sharing a
window compose exactly like sequential row writes.  Row widths must be a
multiple of 128 lanes on the chip (core/schema.py pads the node image);
``interpret=True`` takes any width.

Three kernels, tracking the sync path's evolution toward the paper's
one-contiguous-DMA-per-node transfer:
  * ``snapshot_delta_scatter`` — one [S, W] array per call: the window
    read-modify-write above, done with vector selects in VMEM.
  * ``snapshot_multi_scatter`` — ALL fields of a dirty row in ONE
    ``pallas_call``: each field is its own aliased operand/output pair and
    the grid body DMAs every field's row in the same iteration (the
    ``cfg.layout="legacy"`` parity path; its one-word fields fall below
    the TPU tile, so it runs in interpret mode only).
  * ``snapshot_image_scatter`` — the packed-layout endgame
    (``cfg.layout="packed"``, the default): the snapshot is ONE
    ``[S, image_words]`` u32 image (core/schema.py), a dirty node's entire
    contents are one contiguous ``[image_words]`` row, and the scatter is
    one window read-modify-write per dirty node, with no per-field
    addressing anywhere on the device side.

Shared caveat: duplicate rows must carry identical data (the store pads
deltas with repeats), which keeps the scatters order-free.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

GROUP = 8    # sublane tile: HBM/VMEM rows are DMA'd in windows of 8


def window(ref, row):
    """The tile-aligned 8-row window of ``ref`` holding ``row``."""
    return ref.at[pl.ds(pl.multiple_of(row // GROUP * GROUP, GROUP), GROUP)]


def pad_rows(x, multiple: int = GROUP, fill=None):
    """Pad the leading dim up to ``multiple``: with repeats of the last row
    (idempotent update/entry/request padding) when ``fill`` is None, else
    with rows of ``fill`` (arrays the caller slices back)."""
    extra = -x.shape[0] % multiple
    if not extra:
        return x
    tail = (jnp.repeat(x[-1:], extra, axis=0) if fill is None
            else jnp.full((extra,) + x.shape[1:], fill, x.dtype))
    return jnp.concatenate([x, tail])


def as_i32(x):
    """32-bit words as int32: vector reductions over unsigned ints do not
    lower on the TPU, so u32 patterns are bitcast."""
    return (jax.lax.bitcast_convert_type(x, jnp.int32)
            if x.dtype == jnp.uint32 else x.astype(jnp.int32))


def _scatter_row_kernel(rows_ref, upd_ref, dst_ref, out_ref, win_ref, sem):
    del dst_ref                     # aliased to out_ref
    i = pl.program_id(0)
    r = rows_ref[i]
    win = window(out_ref, r)
    cp = pltpu.make_async_copy(win, win_ref, sem)
    cp.start()
    cp.wait()
    sub = jax.lax.broadcasted_iota(jnp.int32, win_ref.shape, 0)
    upd = as_i32(upd_ref[...])
    row = jnp.sum(jnp.where(sub == i % GROUP, upd, 0), axis=0, keepdims=True)
    new = jnp.where(sub == r % GROUP, row, as_i32(win_ref[...]))
    win_ref[...] = jax.lax.bitcast_convert_type(new, win_ref.dtype)
    cp = pltpu.make_async_copy(win_ref, win, sem)
    cp.start()
    cp.wait()


@functools.partial(jax.jit, static_argnames=("interpret",))
def snapshot_delta_scatter(dst, rows, upd, *, interpret: bool = False):
    """dst[rows[i], :] = upd[i, :] for i in range(D), in place.

    dst:  [S, W] resident device array (flattened trailing dims)
    rows: [D] int32 target rows (repeats allowed with identical data)
    upd:  [D, W] replacement rows
    """
    S, W = dst.shape
    rows = pad_rows(rows.astype(jnp.int32))
    upd = pad_rows(upd)
    dst_p = pad_rows(dst, fill=0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(rows.shape[0],),
        in_specs=[
            pl.BlockSpec((GROUP, W), lambda i, rows: (i // GROUP, 0)),
            pl.BlockSpec(memory_space=pl.ANY),               # dst (alias)
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.VMEM((GROUP, W), dst.dtype),
                        pltpu.SemaphoreType.DMA(())],
    )
    out = pl.pallas_call(
        _scatter_row_kernel,
        name="delta_row_scatter",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(dst_p.shape, dst.dtype),
        input_output_aliases={2: 0},   # dst (arg 2, after rows & upd) -> out
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(rows, upd, dst_p)
    return out if out.shape[0] == S else out[:S]


def snapshot_image_scatter(image, rows, upd, *, interpret: bool = False):
    """image[rows[i], :] = upd[i, :] — ONE image-row write per dirty node
    (the packed layout's whole sync).

    image: [S, image_words] resident packed node images (u32)
    rows:  [D] int32 dirty physical slots (repeats carry identical data)
    upd:   [D, image_words] replacement node images

    The node image IS the transfer unit: every field of the node rides in
    this one row (static offsets, core/schema.py), so the sync needs no
    per-field operands — same aliased row-scatter machinery as
    ``snapshot_delta_scatter``, applied to whole node images.
    """
    return snapshot_delta_scatter(image, rows, upd, interpret=interpret)


def _log_replay_kernel(offs):
    """Kernel body for one log-replay step: entry ``i`` (a marshalled
    [log_entry_words] u32 record, see ``schema.pack_log_entries``) is
    written into image row ``rows[i]`` at the static layout offsets in
    ``offs``, each per-slot log field advanced by ``slots[i] * width``.
    The row's 8-row window is staged in SMEM, where single words are
    addressable, and written back whole — only the entry's own words
    change.  ``nlog`` is stored as ``slots[i] + 1``: the grid runs in order
    and log appends are monotone per row within an epoch, so the last write
    holds the row's final count (padded duplicate entries repeat the same
    record)."""
    kw, vw = offs.key_words, offs.val_words

    def kernel(rows_ref, slots_ref, entry_ref, img_ref, out_ref, win_ref,
               sem):
        del img_ref                      # aliased to out_ref
        i = pl.program_id(0)
        r = rows_ref[i]
        j = slots_ref[i]
        win = window(out_ref, r)
        cp = pltpu.make_async_copy(win, win_ref, sem)
        cp.start()
        cp.wait()
        rr, e = r % GROUP, i % GROUP

        def put(off, k):
            win_ref[rr, off] = entry_ref[e, k]

        for w in range(kw):
            put(offs.log_keys + j * kw + w, w)
        put(offs.log_keylen + j, kw)
        for w in range(vw):
            put(offs.log_vals + j * vw + w, kw + 1 + w)
        put(offs.log_vallen + j, kw + 1 + vw)
        put(offs.log_op + j, kw + vw + 2)
        put(offs.log_backptr + j, kw + vw + 3)
        put(offs.log_hint + j, kw + vw + 4)
        put(offs.log_vdelta + j, kw + vw + 5)
        win_ref[rr, offs.nlog] = (j + 1).astype(win_ref.dtype)
        cp = pltpu.make_async_copy(win_ref, win, sem)
        cp.start()
        cp.wait()
    return kernel


@functools.partial(jax.jit, static_argnames=("offs", "interpret"))
def log_replay_scatter(image, rows, slots, entries, *, offs,
                       interpret: bool = False):
    """Replay one epoch's marshalled log entries into a resident packed
    node image, in place (the log-shipped replication feed's device half).

    image:   [S, image_words] resident follower node images (u32)
    rows:    [D] int32 target physical slots (leaves that took appends)
    slots:   [D] int32 log slot index per entry (monotone per row;
             padded entries repeat the last record)
    entries: [D, log_entry_words] u32 marshalled records
    offs:    ``schema.LogReplayOffsets`` static layout constants

    Where the image-delta feed ships a whole ``image_words`` row per dirty
    node, this kernel moves only each entry's ~(key_words + val_words + 6)
    words over the feed — the device-side analogue of shipping the op wire
    stream instead of node buffers over the slow bus.
    """
    S, W = image.shape
    rows = pad_rows(rows.astype(jnp.int32))
    slots = pad_rows(slots.astype(jnp.int32))
    entries = pad_rows(entries)
    image_p = pad_rows(image, fill=0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(rows.shape[0],),
        in_specs=[
            pl.BlockSpec((GROUP, entries.shape[1]),
                         lambda i, rows, slots: (i // GROUP, 0),
                         memory_space=pltpu.SMEM),          # entry records
            pl.BlockSpec(memory_space=pl.ANY),              # image (alias)
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SMEM((GROUP, W), image.dtype),
                        pltpu.SemaphoreType.DMA(())],
    )
    out = pl.pallas_call(
        _log_replay_kernel(offs),
        name="log_replay_scatter",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(image_p.shape, image.dtype),
        input_output_aliases={3: 0},   # image (after rows, slots, entries)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(rows, slots, entries, image_p)
    return out if out.shape[0] == S else out[:S]


def _multi_scatter_kernel(nf: int):
    """Kernel body for ``nf`` fused fields: refs arrive as
    (rows, upd_0..upd_{nf-1}, dst_0..dst_{nf-1}, out_0..out_{nf-1});
    every field's update row DMAs over its aliased output row."""
    def kernel(rows_ref, *refs):
        del rows_ref  # drives the out index maps; dsts are aliased
        upd = refs[:nf]
        out = refs[2 * nf:]
        for f in range(nf):
            out[f][...] = upd[f][...]
    return kernel


@functools.partial(jax.jit, static_argnames=("interpret",))
def snapshot_multi_scatter(dsts, rows, upd, *, interpret: bool = False):
    """Fused dirty-row scatter: dsts[f][rows[i], :] = upd[f][i, :] for every
    field f, in ONE kernel invocation (the paper's whole-node DMA).

    dsts: sequence of [S, W_f] resident device arrays (trailing dims
          flattened by the caller; dtypes may differ per field)
    rows: [D] int32 target rows (repeats allowed with identical data)
    upd:  matching sequence of [D, W_f] replacement rows

    Returns the new field arrays in input order.  The grid iterates over
    update rows with ``rows`` scalar-prefetched; each destination is
    aliased to its output, so untouched rows keep their contents without
    any copy and the whole sync costs one kernel launch.
    """
    dsts, upd = tuple(dsts), tuple(upd)
    nf = len(dsts)
    D = upd[0].shape[0]

    def upd_spec(w):
        return pl.BlockSpec((1, w), lambda i, rows: (i, 0))

    def out_spec(w):
        return pl.BlockSpec((1, w), lambda i, rows: (rows[i], 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(D,),
        in_specs=[upd_spec(u.shape[1]) for u in upd]
        + [pl.BlockSpec(memory_space=pl.ANY)] * nf,
        out_specs=[out_spec(d.shape[1]) for d in dsts],
    )
    return pl.pallas_call(
        _multi_scatter_kernel(nf),
        name="multi_field_scatter",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(d.shape, d.dtype) for d in dsts],
        # dst f is argument 1 + nf + f (after rows and the nf update blocks)
        input_output_aliases={1 + nf + f: f for f in range(nf)},
        interpret=interpret,
    )(rows, *upd, *dsts)
