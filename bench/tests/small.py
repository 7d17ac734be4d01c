"""A cell shrunk to run on the CPU in seconds: few records, a short
window and a low offered rate.  Everything else is the cell as
committed."""
from __future__ import annotations

from bench.harness import load_cell

RECORDS = 2000
SECONDS = 1.0
RATE = 200.0


def small_cell(name: str, root=None):
    bm, cell, config, mix = (load_cell(name) if root is None
                             else load_cell(name, root))
    mix = dict(mix)
    if "rate" in mix:
        mix["rate"] = RATE
    return bm, cell, config, mix
