"""The plain reference: its semantics by hand, against the store's own
host tree on random writes, and an off-chip rehearsal of both cells
through the harness, the service and the reference end to end (on the
CPU: a rehearsal of the control flow, not a measurement)."""
from __future__ import annotations

import numpy as np

from bench.harness import check, run_cell
from bench.reference import Reference
from bench.tests.small import RECORDS, SECONDS, small_cell


def ref5():
    vals = np.arange(5 * 4, dtype=np.uint8).reshape(5, 4)
    return Reference(vals), vals           # ids 0, 2, 4, 6, 8


def test_floor_start_scan_by_hand():
    ref, vals = ref5()
    k, v = ref.key, lambda i: vals[i // 2].tobytes()
    assert ref.scan(3, 6) == [(k(2), v(2)), (k(4), v(4)), (k(6), v(6))]
    assert ref.scan(4, 5) == [(k(4), v(4))]          # floor is lo itself
    ref.write(2, None)                                # delete the floor
    assert ref.scan(3, 4) == [(k(0), v(0)), (k(4), v(4))]
    ref.write(3, b"new")                              # insert an odd id
    assert ref.scan(3, 4) == [(k(3), b"new"), (k(4), v(4))]
    assert ref.scan(1, 4) == [(k(0), v(0)), (k(3), b"new"), (k(4), v(4))]
    ref.write(0, None)
    assert ref.scan(0, 2) == []                       # no floor, none in (0, 2]
    assert ref.scan(100, 200) == [(k(8), v(8))]       # floor past the end
    assert ref.get(2) is None and ref.get(3) == b"new" and ref.get(7) is None
    ref.write(2, b"back")                             # update is an upsert
    assert ref.get(2) == b"back"


def test_reference_matches_store_host_tree():
    """Same writes on the store's host tree and the reference: every GET
    and floor-start SCAN agrees."""
    from repro.core import HoneycombStore
    rng = np.random.default_rng(5)
    n = 600
    vals = rng.integers(0, 256, (n, 16), dtype=np.uint8)
    ref = Reference(vals)
    store = HoneycombStore()
    for i in rng.permutation(n):
        store.put(ref.key(2 * i), vals[i].tobytes())
    for _ in range(1500):
        i = int(rng.integers(0, 2 * n + 20))
        op = rng.integers(0, 3)
        if op == 0:
            v = rng.integers(0, 256, 16, dtype=np.uint8).tobytes()
            store.update(ref.key(i), v)
            ref.write(i, v)
        elif op == 1:
            store.delete(ref.key(i))
            ref.write(i, None)
    for _ in range(400):
        lo = int(rng.integers(0, 2 * n + 20))
        hi = lo + int(rng.integers(0, 8))
        assert store.get(ref.key(lo)) == ref.get(lo)
        assert store.scan(ref.key(lo), ref.key(hi)) == ref.scan(lo, hi)


def test_rehearsal_end_to_end():
    bm, cell, config, mix = small_cell("cloud-scan.uniform.open")
    out = run_cell(config, mix, 2 ** 31 + 12345, SECONDS, False,
                   records=RECORDS, log=lambda *_: None)
    assert out["check"]["wrong"] == 0
    assert out["check"]["unanswered"] == 0
    assert out["served"] > 0 and out["ctx"]["requests"] > 0


def test_check_counts_an_altered_ticket():
    from bench.harness import Session, warm_reads
    bm, cell, config, mix = small_cell("cloud-scan.uniform.open")
    s = Session(config, mix, 7, RECORDS, log=lambda *_: None)
    warm_reads(s.client, s.gen, mix, 4, s.width)    # SCAN epochs of 1, 2, 4
    epochs = s.close()
    assert check(epochs, s.records, s.width)["wrong"] == 0
    status, items = epochs[0].answers[0]
    assert items
    epochs[0].answers[0] = (status, items[:-1])
    assert check(epochs, s.records, s.width)["wrong"] == 1
    epochs[1].answers[1] = None
    assert check(epochs, s.records, s.width)["unanswered"] == 1
