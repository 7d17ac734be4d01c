"""Faults planted under a run, to show that the check catches them.

Each function takes the set-up ``Session`` (bench/harness.py) and breaks
the store underneath through its public methods; none is used by a
measured run.  ``stale_snapshot`` is the control (bench/control.py): it
breaks the guarantee the configurations state — an acknowledged write is
read back — the way a later change might be tempted to, by skipping the
sync that publishes writes to the chip.  The others are the faults the
tests in ``bench/tests/test_faults.py`` plant.
"""
from __future__ import annotations

from bench.traffic.generator import UPDATE


def stale_snapshot(session, updates: int = 1024) -> None:
    """Control: the store stops syncing, then the client updates
    ``updates`` keys drawn by the configuration's request distribution
    (hot keys, under zipf).  Every acknowledged write after this point is
    left out of the chip's snapshot, so reads of those keys are stale."""
    store = session.store
    store.begin_export = lambda *a, **k: False
    reqs = session.gen.requests(updates)
    reqs.kind[:] = UPDATE
    reqs.hi[:] = reqs.key
    reqs.items[:] = 1
    session.client.serve(reqs, session.ops(reqs), measured=False)


def unchanged_state(session) -> None:
    """A sync step that returns its state unchanged: writes are admitted
    and acknowledged, the chip's snapshot never moves."""
    session.store.begin_export = lambda *a, **k: False


def _wrap_reads(store, change) -> None:
    get_batch, scan_batch = store.get_batch, store.scan_batch

    def get(keys, **kw):
        return change(list(get_batch(keys, **kw)), None)

    def scan(ranges, **kw):
        return change(list(scan_batch(ranges, **kw)), [])

    store.get_batch, store.scan_batch = get, scan


def half_batch(session) -> None:
    """Half of every read batch left out: the second half of the lanes
    comes back empty (GET not found, SCAN no items)."""
    def change(out, empty):
        half = (len(out) + 1) // 2
        return out[:half] + [empty] * (len(out) - half)
    _wrap_reads(session.store, change)


def altered_answer(session) -> None:
    """One answer per read batch altered where it is produced: the first
    lane's value has its last byte flipped."""
    def flip(v: bytes) -> bytes:
        return v[:-1] + bytes([v[-1] ^ 0xFF]) if v else b"\x00"

    def change(out, empty):
        if not out:
            return out
        first = out[0]
        if empty is None:                       # GET: a value or None
            out[0] = flip(first or b"")
        elif first:                             # SCAN: (key, value) items
            k, v = first[0]
            out[0] = [(k, flip(v))] + list(first[1:])
        else:
            out[0] = [(b"\x00" * 8, b"\x00")]
        return out
    _wrap_reads(session.store, change)


FAULTS = {"stale_snapshot": stale_snapshot, "unchanged_state": unchanged_state,
          "half_batch": half_batch, "altered_answer": altered_answer}
