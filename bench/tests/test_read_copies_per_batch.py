"""The read-dispatch copy counter's reader: nothing from a program that
lacks the counters or dispatched no device read batch, the ratio
otherwise."""
from __future__ import annotations

import pytest

from bench.metrics import read_copies_per_batch as m


@pytest.mark.parametrize("shard,want", [
    ({"dispatched_lanes": 900, "fetch_s": 1.0}, None),
    ({"read_batches": 0, "read_copies": 0}, None),
    ({"read_batches": 440, "read_copies": 440}, 1.0),
    ({"read_batches": 440, "read_copies": 3080}, 7.0)])
def test_read_copies_per_batch(shard, want):
    assert m.read({"shard": shard}) == want
