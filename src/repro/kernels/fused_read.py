"""Fused device-resident GET/SCAN megakernels (paper Sections 4-5).

One ``pallas_call`` executes the WHOLE per-request traversal — multi-level
descend over the packed node image, leaf resolve, order-hint log merge,
MVCC version resolution — where the reference path (core/read_path.py)
issues one gather storm per level.  A read batch costs ONE device dispatch
regardless of tree height or scan budget.

Structure.  The grid runs over blocks of ``GROUP`` = 8 requests (the TPU
sublane tile: the per-request key and length blocks are [8, key_words] and
[8, 1]); each program walks its eight requests one after another.  A
traversal is pointer chasing — page-table entry, node row, old-version
pointer, child LID, sibling LID — so it runs on the TPU's scalar unit out
of SMEM, where any word of a node is addressable:

  * node fetch: the heap image and the page table stay in HBM (``pl.ANY``)
    and are read by explicit DMA.  HBM is tiled 8 rows x 128 lanes and a
    DMA window must be tile-aligned, so a fetch copies the 8-row window
    holding the node (``image_words`` is padded to a multiple of 128 lanes,
    core/schema.py) into an SMEM scratch window and addresses the row
    inside it;
  * cache tier: the snapshot's ``[cache_slots, image_words]`` cache array
    (root + top interior levels, packed at export — core/cache.py /
    ``attach_cache_image``) arrives through a plain VMEM BlockSpec, pinned
    on-core for every program.  The cached-LID probe is one vector compare
    against the VMEM-resident LID vector; a hit copies its window VMEM ->
    SMEM, and the heap DMA, page-table lookup and MVCC walk are genuinely
    not executed.  The compile-time ``lb_fraction`` knob deterministically
    routes a slice of cache-HIT requests down the heap pipe anyway
    (Section 5's dual-pipe load balancer); per-request
    ``[vmem_hits, heap_gathers, lb_routed]`` meters come back as an output
    block;
  * SCAN leaf resolve: the RSU order-hint log sort (one shift-register
    step per log entry), the merge of log entries into the sorted block by
    back pointer, and the newest-visible-version shadow resolution walk
    the merged leaf once in key order; only the used slots are visited;
  * GET leaf resolve: a point lookup in the descended leaf alone — the
    sorted-block floor item if its key is K, and one equality test per log
    entry, the newest visible version winning (``_get_kernel``).  GET is
    SCAN(K, K) plus an equality post-pass in the oracles
    (``ref.batched_get_fused_ref``, ``read_path.batched_get``), not here.

The bodies re-implement the search arithmetic of core/read_path.py
(``_shortcut_floor``/``_segment_floor``/``_resolve_leaf``/
``scan_from_leaf``) as scalar loops with identical answers, ties and
edge cases included; interpret-mode parity against the jnp oracles
(``kernels/ref.py`` ``batched_*_fused_ref``) pins them bit for bit.  The
oracle is what XLA:CPU lowers; ``interpret=True`` is the CPU-testable
kernel path, compiled Mosaic the TPU one.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import read_path as _rp
from repro.core.heap import LEAF, LOG_DELETE, NULL
from repro.core.schema import NodeImageLayout

from .delta_scatter import GROUP, as_i32, pad_rows, window

PT_LANES = 128     # the page table is viewed as [*, 128] lane rows
_SIGN = -2 ** 31   # xor with this maps u32 order onto i32 order
_INT_MIN = -2 ** 31


def _sign(d):
    return jnp.where(d > 0, 1, jnp.where(d < 0, -1, 0))


def _key_cmp(a, alen, b, blen):
    """``core/keys.jax_key_cmp`` on scalars: ``a``/``b`` are lists of
    key_words i32 scalars holding u32 lanes.  The sign of the first
    differing lane (unsigned), else the sign of the length difference."""
    res = jnp.int32(0)
    for x, y in zip(reversed(a), reversed(b)):
        lt = (x ^ _SIGN) < (y ^ _SIGN)
        res = jnp.where(x != y, jnp.where(lt, -1, 1), res)
    return jnp.where(res != 0, res, _sign(alen - blen))


def _key_eq(a, alen, b, blen):
    """Key equality on scalars: every lane and the length equal (what
    ``_key_cmp(...) == 0`` says, without the order)."""
    eq = alen == blen
    for x, y in zip(a, b):
        eq = eq & (x == y)
    return eq


class _Traversal:
    """What the GET and SCAN bodies share: windowed DMAs into SMEM, the
    heap pipe (page table + MVCC old-version walk), a node's floor search
    and the cache-tiered descend.  Built once per grid program, over the
    program's refs and its node / page-table windows."""

    def __init__(self, cfg, routed_k, n_cache, scal_ref, clids_ref, cimg_ref,
                 pt_ref, img_ref, node, ptw, sem):
        layout = NodeImageLayout.for_config(cfg)
        self.off = {name: s.offset for name, s in layout.slots.items()}
        self.cfg, self.routed_k, self.n_cache = cfg, routed_k, n_cache
        self.cimg_ref, self.pt_ref, self.img_ref = cimg_ref, pt_ref, img_ref
        self.node, self.ptw, self.sem = node, ptw, sem
        self.root, self.rv = scal_ref[0], scal_ref[1]
        self.clids = clids_ref[...]                     # [1, C] (VMEM)
        self.cache_iota = jax.lax.broadcasted_iota(jnp.int32,
                                                   self.clids.shape, 1)

    # ---- memory: windowed DMAs into SMEM ----------------------------------
    def dma(self, src, dst):
        cp = pltpu.make_async_copy(src, dst, self.sem)
        cp.start()
        cp.wait()

    def load_heap(self, p):
        self.dma(window(self.img_ref, p), self.node)
        return p % GROUP

    def load_cache(self, slot):
        self.dma(window(self.cimg_ref, slot), self.node)
        return slot % GROUP

    def rd(self, r, k):               # one image word, as its i32 pattern
        return self.node[r, k].astype(jnp.int32)     # same-width: bits kept

    def word(self, r, name, k=0):
        return self.rd(r, self.off[name] + k)

    def node_key(self, r, base):
        return [self.rd(r, base + w) for w in range(self.cfg.key_words)]

    def fetch_lid(self, lid):
        """Page-table lookup + MVCC old-version walk + row fetch — the
        heap pipe.  Returns (resolved phys, window row)."""
        word, rv = self.word, self.rv
        lid = jnp.maximum(lid, 0)
        self.dma(window(self.pt_ref, lid // PT_LANES), self.ptw)
        p0 = jnp.maximum(self.ptw[lid // PT_LANES % GROUP, lid % PT_LANES], 0)

        # the predicate is carried, not read in the loop condition:
        # a while-loop condition must not read refs
        def too_new(r):
            return (word(r, "version") > rv) & (word(r, "oldptr") != NULL)

        def cond(st):
            return (st[0] < self.cfg.max_version_chain) & st[3]

        def older(st):
            hops, _, r, _ = st
            p = jnp.maximum(word(r, "oldptr"), 0)
            r = self.load_heap(p)
            return hops + 1, p, r, too_new(r)

        r0 = self.load_heap(p0)
        _, p, r, _ = jax.lax.while_loop(
            cond, older, (jnp.int32(0), p0, r0, too_new(r0)))
        return p, r

    # ---- descend: cache tier first, heap fall-through ---------------------
    def floor_of(self, r, q, qlen):
        """Shortcut floor, then segment floor: the index of the last
        sorted-block item whose key is <= q, or -1."""
        cfg, off, word, node_key = self.cfg, self.off, self.word, self.node_key
        N, KW = cfg.node_cap, cfg.key_words
        nsc = word(r, "n_shortcuts")

        def sc_step(s, idx):
            c = _key_cmp(node_key(r, off["sc_keys"] + s * KW),
                         word(r, "sc_keylen", s), q, qlen)
            return jnp.where((s < nsc) & (c <= 0), s, idx)

        seg = jnp.maximum(
            jax.lax.fori_loop(0, cfg.n_shortcuts, sc_step, jnp.int32(-1)), 0)
        base = word(r, "sc_pos", seg)
        n = word(r, "nitems")

        def seg_step(t, local):
            o = base + t
            oc = jnp.minimum(o, N - 1)
            oc = jnp.where(oc < 0, oc + N, oc)
            c = _key_cmp(node_key(r, off["skeys"] + oc * KW),
                         word(r, "skeylen", oc), q, qlen)
            return jnp.where((o < n) & (c <= 0), t, local)

        local = jax.lax.fori_loop(0, cfg.segment_items, seg_step,
                                  jnp.int32(-1))
        return jnp.where(local >= 0, base + local, -1)

    def child_of(self, r, q, qlen):
        """The floor item's child LID, else the left child."""
        idx = self.floor_of(r, q, qlen)
        return jnp.where(
            idx >= 0,
            self.word(r, "svals", jnp.maximum(idx, 0) * self.cfg.val_words),
            self.word(r, "left_child"))

    def descend(self, lane, q, qlen):
        """Returns (leaf source is_cache, cache slot or heap phys, window
        row, [vmem_hits, heap_gathers, lb_routed])."""
        routed = (lane % 16) < self.routed_k
        clids, n_cache = self.clids, self.n_cache

        def cond(st):
            return (st[0] < self.cfg.max_height) & (st[1] == 0)

        def level(st):
            lvl, _, lid, _, _, _, vh, hg, lr = st
            eq = clids == lid
            hit = (jnp.max(eq.astype(jnp.int32)) > 0) & (lid != NULL)
            slot = jnp.min(jnp.where(eq, self.cache_iota, n_cache))
            use_cache = hit & ~routed

            def from_cache():
                return slot, self.load_cache(slot)

            src, r = jax.lax.cond(use_cache, from_cache,
                                  lambda: self.fetch_lid(lid))
            done = (self.word(r, "ntype") == LEAF).astype(jnp.int32)
            child = self.child_of(r, q, qlen)
            return (lvl + 1, done, jnp.where(done == 1, lid, child),
                    use_cache.astype(jnp.int32), src, r,
                    vh + use_cache.astype(jnp.int32),
                    hg + (~use_cache).astype(jnp.int32),
                    lr + (hit & routed).astype(jnp.int32))

        z = jnp.int32(0)
        st = jax.lax.while_loop(cond, level,
                                (z, z, self.root, z, z, z, z, z, z))
        return st[3], st[4], st[5], (st[6], st[7], st[8])


def _get_kernel(cfg, routed_k: int, n_req: int, n_cache: int):
    """GET(K) body: descend to K's leaf, then resolve K in that leaf alone.

    The candidates are the sorted-block floor item, if its key is K (it
    has the node's version), and every log entry of key K whose version
    ``nv + vdelta`` the read version sees.  The newest wins; a later log
    slot wins a tie and beats the sorted item, as in the host tree's
    ``get`` (each write takes its own version, so ties do not occur).  No
    candidate, or a delete marker winning, answers not-found with zero
    value words.  No other leaf can hold K: the descend's separator floor
    puts every key of the tree at this read version in this leaf, and a
    left sibling holds only keys strictly below its separator.  ``n_req``
    is the unpadded batch size: padded lanes do no work and meter
    nothing."""
    L, KW, VW = cfg.log_cap, cfg.key_words, cfg.val_words

    def kernel(scal_ref, key_ref, klen_ref, clids_ref, cimg_ref, pt_ref,
               img_ref, found_ref, vals_ref, vlens_ref, meters_ref, node,
               ptw, sem):
        tr = _Traversal(cfg, routed_k, n_cache, scal_ref, clids_ref,
                        cimg_ref, pt_ref, img_ref, node, ptw, sem)
        rd, word, node_key, off = tr.rd, tr.word, tr.node_key, tr.off

        def request(j, lane):
            q = [key_ref[j, w] for w in range(KW)]
            qlen = klen_ref[j, 0]
            _, _, r, meters = tr.descend(lane, q, qlen)
            nv = word(r, "version")
            i = tr.floor_of(r, q, qlen)
            ic = jnp.maximum(i, 0)
            in_sorted = (i >= 0) & _key_eq(
                node_key(r, off["skeys"] + ic * KW), word(r, "skeylen", ic),
                q, qlen)

            def log_step(e, best):
                ver = nv + word(r, "log_vdelta", e)
                hit = (ver <= tr.rv) & (ver >= best[0]) & _key_eq(
                    node_key(r, off["log_keys"] + e * KW),
                    word(r, "log_keylen", e), q, qlen)
                return jnp.where(hit, ver, best[0]), jnp.where(hit, e, best[1])

            nlg = jnp.clip(word(r, "nlog"), 0, L)
            _, e = jax.lax.fori_loop(
                0, nlg, log_step,
                (jnp.where(in_sorted, nv, _INT_MIN), jnp.int32(-1)))
            in_log = e >= 0
            ec = jnp.maximum(e, 0)
            found = jnp.where(in_log, word(r, "log_op", ec) != LOG_DELETE,
                              in_sorted)
            vb = jnp.where(in_log, off["log_vals"] + ec * VW,
                           off["svals"] + ic * VW)
            vlen = jnp.where(in_log, word(r, "log_vallen", ec),
                             word(r, "svallen", ic))
            return (found.astype(jnp.int32),
                    [rd(r, vb + w) for w in range(VW)], vlen, meters)

        blk = pl.program_id(0)

        def lane_step(j, _):
            lane = blk * GROUP + j

            def skip():
                z = jnp.int32(0)
                return z, [z] * VW, z, (z, z, z)

            found, vals, vlen, meters = jax.lax.cond(
                lane < n_req, lambda: request(j, lane), skip)
            for k in range(3):
                meters_ref[j, k] = meters[k]
            found_ref[j, 0] = found
            for w in range(VW):
                vals_ref[j, w] = vals[w] * found
            vlens_ref[j, 0] = vlen * found
            return 0

        jax.lax.fori_loop(0, GROUP, lane_step, 0)

    return kernel


def _scan_kernel(cfg, routed_k: int, n_req: int, n_cache: int):
    """SCAN(K_l, K_u) body: descend to K_l's leaf, a floor pre-pass (walk
    left until a visible key <= K_l), then the forward walk across right
    siblings, each leaf resolved in merged key order.  ``n_req`` is the
    unpadded batch size: padded lanes do no work and meter nothing."""
    N, L = cfg.node_cap, cfg.log_cap
    M = cfg.max_scan_items
    KW, VW = cfg.key_words, cfg.val_words
    MSL = cfg.max_scan_leaves

    def kernel(scal_ref, lo_ref, lolen_ref, hi_ref, hilen_ref, clids_ref,
               cimg_ref, pt_ref, img_ref, *refs):
        outs = refs[:7]
        (node, ptw, lpos, lrank, lord, seq, runid, runmax, okeys, oklens,
         ovals, ovlens, fitem, sem) = refs[7:]
        tr = _Traversal(cfg, routed_k, n_cache, scal_ref, clids_ref,
                        cimg_ref, pt_ref, img_ref, node, ptw, sem)
        rd, word, node_key, off = tr.rd, tr.word, tr.node_key, tr.off
        rv = tr.rv

        # ---- leaf resolve: merged, shadow-resolved walk in key order ------
        def item_addr(r, t):
            """(key base, key len, value base, value len, is-log, log slot)
            of merged item ``t`` (< N sorted block, else log slot t - N)."""
            is_log = t >= N
            j = jnp.maximum(t - N, 0)
            kb = jnp.where(is_log, off["log_keys"] + j * KW,
                           off["skeys"] + t * KW)
            klen = jnp.where(is_log, word(r, "log_keylen", j),
                             word(r, "skeylen", jnp.minimum(t, N - 1)))
            vb = jnp.where(is_log, off["log_vals"] + j * VW,
                           off["svals"] + t * VW)
            vlen = jnp.where(is_log, word(r, "log_vallen", j),
                             word(r, "svallen", jnp.minimum(t, N - 1)))
            return kb, klen, vb, vlen, is_log, j

        def walk_leaf(r, consume, carry):
            """Feed the leaf's items to ``consume(r, carry, item, live)`` in
            merged key order — exactly the order and liveness
            ``read_path._resolve_leaf`` produces for the used slots (unused
            slots are never live, so they are not visited)."""
            nit = jnp.clip(word(r, "nitems"), 0, N)
            nlg = jnp.clip(word(r, "nlog"), 0, L)
            nv = word(r, "version")

            # RSU log sort: one shift-register step per entry (Fig. 8)
            def sort_step(j, _):
                h = word(r, "log_hint", j)

                def shift(k, __):
                    lpos[k] = lpos[k] + (lpos[k] >= h).astype(jnp.int32)
                    return 0

                jax.lax.fori_loop(0, j, shift, 0)
                lpos[j] = h
                return 0

            jax.lax.fori_loop(0, nlg, sort_step, 0)

            def rank_step(j, _):
                lrank[j] = word(r, "log_backptr", j) * (L + 1) + lpos[j]
                return 0

            jax.lax.fori_loop(0, nlg, rank_step, 0)

            def order_step(j, _):          # stable rank order of the log
                def before(k, n):
                    rk = lrank[k]
                    return n + ((rk < lrank[j])
                                | ((rk == lrank[j]) & (k < j))
                                ).astype(jnp.int32)

                lord[jax.lax.fori_loop(0, nlg, before, 0)] = j
                return 0

            jax.lax.fori_loop(0, nlg, order_step, 0)

            # merge by rank: a sorted item wins ties (lower concat index)
            def merge_step(p, st):
                i, q = st
                # clamped: once the log is exhausted the slot read is stale
                # (unused) and must still address inside ``lrank``
                lq = jnp.clip(lord[jnp.minimum(q, L - 1)], 0, L - 1)
                take = (i < nit) & ((q >= nlg)
                                    | (i * (L + 1) + L <= lrank[lq]))
                seq[p] = jnp.where(take, i, N + lq)
                return (i + take.astype(jnp.int32),
                        q + (~take).astype(jnp.int32))

            U = nit + nlg
            jax.lax.fori_loop(0, U, merge_step, (jnp.int32(0), jnp.int32(0)))

            def vmask_of(t):
                kb, klen, vb, vlen, is_log, j = item_addr(r, t)
                vers = jnp.where(is_log, nv + word(r, "log_vdelta", j), nv)
                vis = ~is_log | (vers <= rv)
                return (jnp.where(vis, vers, _INT_MIN), vis,
                        is_log & (word(r, "log_op", j) == LOG_DELETE))

            # runs of equal adjacent keys and each run's newest visible
            def run_step(p, run):
                t = seq[p]
                kb, klen = item_addr(r, t)[:2]
                pkb, pklen = item_addr(r, seq[jnp.maximum(p - 1, 0)])[:2]
                same = (p > 0) & (_key_cmp(node_key(r, kb), klen,
                                           node_key(r, pkb), pklen) == 0)
                run = jnp.where(same, run, run + 1)
                vm = vmask_of(t)[0]
                runmax[run] = jnp.where(same, jnp.maximum(runmax[run], vm),
                                        vm)
                runid[p] = run
                return run

            jax.lax.fori_loop(0, U, run_step, jnp.int32(-1))

            def emit_step(p, carry):
                t = seq[p]
                vm, vis, isdel = vmask_of(t)
                live = vis & (vm == runmax[runid[p]]) & ~isdel
                return consume(r, carry, t, live)

            return jax.lax.fori_loop(0, U, emit_step, carry)

        # an item record is [key words, key len, value words, value len]
        def copy_item(r, t, put):
            kb, klen, vb, vlen = item_addr(r, t)[:4]
            for w in range(KW):
                put(w, rd(r, kb + w))
            put(KW, klen)
            for w in range(VW):
                put(KW + 1 + w, rd(r, vb + w))
            put(KW + 1 + VW, vlen)

        def put_fitem(k, v):
            fitem[k] = v

        def put_slot(m):
            def put(k, v):
                if k < KW:
                    okeys[m * KW + k] = v
                elif k == KW:
                    oklens[m] = v
                elif k < KW + 1 + VW:
                    ovals[m * VW + k - KW - 1] = v
                else:
                    ovlens[m] = v
            return put

        # ---- one request ---------------------------------------------------
        def request(j, lane):
            lo = [lo_ref[j, w] for w in range(KW)]
            lolen = lolen_ref[j, 0]
            hi = [hi_ref[j, w] for w in range(KW)]
            hilen = hilen_ref[j, 0]

            def zero_slot(m, _):
                for w in range(KW):
                    okeys[m * KW + w] = jnp.int32(0)
                for w in range(VW):
                    ovals[m * VW + w] = jnp.int32(0)
                oklens[m] = jnp.int32(0)
                ovlens[m] = jnp.int32(0)
                return 0

            jax.lax.fori_loop(0, M, zero_slot, 0)
            is_cache, src, r0, meters = tr.descend(lane, lo, lolen)

            # floor pre-pass: walk left until a visible key <= lo
            def floor_consume(r, cand, t, live):
                kb, klen = item_addr(r, t)[:2]
                c = _key_cmp(node_key(r, kb), klen, lo, lolen)
                return jnp.where(live & (c <= 0), t, cand)

            def floor_cond(st):
                return st[3] == 0

            def floor_step(st):
                step, have, moved, _, r = st
                cand = walk_leaf(r, floor_consume, jnp.int32(-1))
                found = cand >= 0

                @pl.when(found & (have == 0))
                def _():
                    copy_item(r, cand, put_fitem)

                have = jnp.maximum(have, found.astype(jnp.int32))
                nxt = word(r, "lsib")
                move = (have == 0) & (nxt != NULL) & (step < MSL - 1)

                def go():
                    return tr.fetch_lid(nxt)[1]

                r = jax.lax.cond(move, go, lambda: r)
                return (step + 1, have, moved | move.astype(jnp.int32),
                        (~move).astype(jnp.int32), r)

            z = jnp.int32(0)
            _, have, moved, _, _ = jax.lax.while_loop(
                floor_cond, floor_step, (z, z, z, z, r0))
            fkey = [fitem[w] for w in range(KW)]
            emit_floor = (have == 1) & (
                _key_cmp(fkey, fitem[KW], hi, hilen) <= 0)

            @pl.when(emit_floor)
            def _():
                put = put_slot(0)
                for k in range(KW + VW + 2):
                    put(k, fitem[k])

            count0 = emit_floor.astype(jnp.int32)

            # forward scan across sibling leaves, from the descend's leaf
            @pl.when(moved == 1)
            def _():
                jax.lax.cond(is_cache == 1, lambda: tr.load_cache(src),
                             lambda: tr.load_heap(src))

            def fwd_consume(r, carry, t, live):
                count, trunc, past = carry
                kb, klen = item_addr(r, t)[:2]
                key = node_key(r, kb)
                c_lo = _key_cmp(key, klen, lo, lolen)
                c_hi = _key_cmp(key, klen, hi, hilen)
                emit = live & (c_lo > 0) & (c_hi <= 0)
                ok = emit & (count < M)

                @pl.when(ok)
                def _():
                    copy_item(r, t, put_slot(count))

                return (count + ok.astype(jnp.int32),
                        trunc | (emit & ~ok).astype(jnp.int32),
                        past | (live & (c_hi > 0)).astype(jnp.int32))

            def fwd_cond(st):
                return (st[0] < MSL) & (st[1] == 0)

            def fwd_step(st):
                step, _, count, trunc, r = st
                count, trunc, past = walk_leaf(r, fwd_consume,
                                               (count, trunc, jnp.int32(0)))
                nxt = word(r, "rsib")
                done = (past == 1) | (nxt == NULL) | (trunc == 1)

                def go():
                    return tr.fetch_lid(nxt)[1]

                r = jax.lax.cond(~done & (step < MSL - 1), go, lambda: r)
                return step + 1, done.astype(jnp.int32), count, trunc, r

            _, done, count, trunc, _ = jax.lax.while_loop(
                fwd_cond, fwd_step, (z, z, count0, z, r0))
            trunc = trunc | (1 - done)
            return count, trunc, meters

        # ---- the block's requests -------------------------------------------
        blk = pl.program_id(0)

        def lane_step(j, _):
            lane = blk * GROUP + j

            def run():
                return request(j, lane)

            def skip():
                z = jnp.int32(0)
                return z, z, (z, z, z)

            count, trunc, meters = jax.lax.cond(lane < n_req, run, skip)
            count_ref, keys_ref, klens_ref, vals_ref, vlens_ref, \
                trunc_ref, meters_ref = outs
            for k in range(3):
                meters_ref[j, k] = meters[k]
            count_ref[j, 0] = count
            trunc_ref[j, 0] = trunc

            def copy_slot(m, __):
                live = (lane < n_req).astype(jnp.int32)
                for w in range(KW):
                    keys_ref[j, m, w] = okeys[m * KW + w] * live
                for w in range(VW):
                    vals_ref[j, m, w] = ovals[m * VW + w] * live
                klens_ref[j, m] = oklens[m] * live
                vlens_ref[j, m] = ovlens[m] * live
                return 0

            jax.lax.fori_loop(0, M, copy_slot, 0)
            return 0

        jax.lax.fori_loop(0, GROUP, lane_step, 0)

    return kernel


def _fused_call(mode, image, pagetable, root_lid, read_version, cache_lids,
                cache_image, keys, cfg, lb_fraction, interpret):
    """Shared launcher: pads the request batch to whole 8-request blocks,
    the cache tier to whole 8-row windows and the page table to whole
    [8, 128] windows.  ``keys`` is [(lanes, lens)] for a GET, [(lo, lolen),
    (hi, hilen)] for a SCAN.  The heap image passes through untouched
    (u32; the kernel reinterprets each word it loads)."""
    B = keys[0][0].shape[0]
    IW = image.shape[1]
    M, KW, VW = cfg.max_scan_items, cfg.key_words, cfg.val_words
    lanes = [pad_rows(as_i32(k)) for k, _ in keys]
    lens = [pad_rows(as_i32(n).reshape(-1, 1)) for _, n in keys]
    Bp = lanes[0].shape[0]
    img = pad_rows(image, fill=0)
    clids = pad_rows(as_i32(cache_lids), fill=NULL)
    C = clids.shape[0]
    cimg = pad_rows(cache_image, fill=0)
    pt = pad_rows(as_i32(pagetable), GROUP * PT_LANES, NULL)
    pt = pt.reshape(-1, PT_LANES)
    scal = jnp.stack([root_lid.astype(jnp.int32),
                      read_version.astype(jnp.int32)])
    T = cfg.node_cap + cfg.log_cap
    L = cfg.log_cap

    def smem(shape):
        nd = len(shape)
        return pl.BlockSpec((GROUP,) + shape,
                            lambda i, s: (i,) + (0,) * nd,
                            memory_space=pltpu.SMEM)

    windows = [
        pltpu.SMEM((GROUP, IW), image.dtype),         # node window
        pltpu.SMEM((GROUP, PT_LANES), jnp.int32),     # page-table window
    ]
    if mode == "scan":
        out_shapes = [(1,), (M, KW), (M,), (M, VW), (M,), (1,), (3,)]
        build = _scan_kernel
        scratch = windows + [
            pltpu.SMEM((L,), jnp.int32),                  # log sort positions
            pltpu.SMEM((L,), jnp.int32),                  # log ranks
            pltpu.SMEM((L,), jnp.int32),                  # log rank order
            pltpu.SMEM((T,), jnp.int32),                  # merged item order
            pltpu.SMEM((T,), jnp.int32),                  # run id per item
            pltpu.SMEM((T,), jnp.int32),                  # run newest version
            pltpu.SMEM((M * KW,), jnp.int32),             # result keys
            pltpu.SMEM((M,), jnp.int32),                  # result key lens
            pltpu.SMEM((M * VW,), jnp.int32),             # result values
            pltpu.SMEM((M,), jnp.int32),                  # result value lens
            pltpu.SMEM((KW + VW + 2,), jnp.int32),        # floor item
        ]
    else:
        out_shapes = [(1,), (VW,), (1,), (3,)]
        build = _get_kernel
        scratch = windows
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Bp // GROUP,),
        in_specs=[smem((KW,)), smem((1,))] * len(keys) + [
            pl.BlockSpec((1, C), lambda i, s: (0, 0),     # cache lids
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((C, IW), lambda i, s: (0, 0),    # cache image
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),            # page table
            pl.BlockSpec(memory_space=pl.ANY),            # heap image
        ],
        out_specs=[smem(s) for s in out_shapes],
        scratch_shapes=scratch + [pltpu.SemaphoreType.DMA(())],
    )
    key_args = [a for pair in zip(lanes, lens) for a in pair]
    return pl.pallas_call(
        build(cfg, int(round(lb_fraction * 16)), B, C),
        name=f"fused_read_{mode}",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((Bp,) + s, jnp.int32)
                   for s in out_shapes],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(scal, *key_args, clids.reshape(1, C), cimg, pt, img)


def _u32(x):
    return jax.lax.bitcast_convert_type(x, jnp.uint32)


@functools.partial(jax.jit, static_argnames=("cfg", "lb_fraction",
                                             "interpret"))
def batched_scan_fused(image, pagetable, root_lid, read_version, cache_lids,
                       cache_image, lo, lolen, hi, hilen, *, cfg,
                       lb_fraction: float = 0.0, interpret: bool = False):
    """Fused SCAN(K_l, K_u): ONE dispatch for the whole batch.  Returns
    (ScanResult, meters i32[3]) matching ``ref.batched_scan_fused_ref``."""
    B = lo.shape[0]
    count, keys, klens, vals, vlens, trunc, meters = _fused_call(
        "scan", image, pagetable, root_lid, read_version, cache_lids,
        cache_image, [(lo, lolen), (hi, hilen)], cfg, lb_fraction,
        interpret)
    res = _rp.ScanResult(count[:B, 0], _u32(keys[:B]), klens[:B],
                         _u32(vals[:B]), vlens[:B], trunc[:B, 0] != 0)
    return res, meters[:B].sum(axis=0)


@functools.partial(jax.jit, static_argnames=("cfg", "lb_fraction",
                                             "interpret"))
def batched_get_fused(image, pagetable, root_lid, read_version, cache_lids,
                      cache_image, key, klen, *, cfg,
                      lb_fraction: float = 0.0, interpret: bool = False):
    """Fused GET(K): ONE dispatch for the whole batch.  Returns
    (GetResult, meters i32[3]) matching ``ref.batched_get_fused_ref``."""
    B = key.shape[0]
    found, vals, vlens, meters = _fused_call(
        "get", image, pagetable, root_lid, read_version, cache_lids,
        cache_image, [(key, klen)], cfg, lb_fraction, interpret)
    res = _rp.GetResult(found[:B, 0] != 0, _u32(vals[:B]), vlens[:B, 0])
    return res, meters[:B].sum(axis=0)
