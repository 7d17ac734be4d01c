"""Plain reference of the store's semantics, for the check that decides
``correct``.

It holds the loaded records as they were made from the seed (id ``2 i``
has value ``values[i]``) and an overlay of the writes applied since:
UPDATE and PUT set a value (both upsert), DELETE removes the key.  GET
returns the value or None.  SCAN(lo, hi) follows the store's floor-start
semantics (Honeycomb, arXiv:2303.14259, Section 3.3): the largest present
key <= lo first, if there is one, then every present key in (lo, hi], in
key order.  Keys are record ids as 8-byte big-endian integers.

It shares no code with the program under test and takes nothing the
program made.
"""
from __future__ import annotations

import bisect

import numpy as np


class Reference:
    def __init__(self, values: np.ndarray, width: int = 8):
        self.values = values              # [n, value_bytes] uint8
        self.n = len(values)
        self.width = width
        self.overlay: dict[int, bytes | None] = {}
        self.extra: list[int] = []        # sorted ids written outside the
        #   loaded set (odd ids, or ids past the end)

    def key(self, i: int) -> bytes:
        return int(i).to_bytes(self.width, "big")

    def _loaded(self, i: int) -> bool:
        return i % 2 == 0 and 0 <= i < 2 * self.n

    # ------------------------------------------------------------ writes
    def write(self, i: int, value: bytes | None) -> None:
        """Upsert ``value`` at id ``i`` (None deletes it)."""
        if not self._loaded(i) and i not in self.overlay:
            bisect.insort(self.extra, i)
        self.overlay[i] = value

    # ------------------------------------------------------------- reads
    def get(self, i: int) -> bytes | None:
        if i in self.overlay:
            return self.overlay[i]
        if self._loaded(i):
            return self.values[i // 2].tobytes()
        return None

    def _floor(self, lo: int) -> int | None:
        """Largest present id <= lo."""
        best = None
        e = min(lo - (lo % 2), 2 * self.n - 2)
        while e >= 0:
            if self.overlay.get(e, b"") is not None:   # absent = loaded
                best = e
                break
            e -= 2
        j = bisect.bisect_right(self.extra, lo) - 1
        while j >= 0 and (best is None or self.extra[j] > best):
            if self.overlay[self.extra[j]] is not None:
                best = self.extra[j]
                break
            j -= 1
        return best

    def scan(self, lo: int, hi: int) -> list[tuple[bytes, bytes]]:
        after = []                         # present ids in (lo, hi]
        e = lo + 1 + (lo + 1) % 2          # first even id > lo
        while e <= min(hi, 2 * self.n - 2):
            if self.overlay.get(e, b"") is not None:
                after.append(e)
            e += 2
        j = bisect.bisect_right(self.extra, lo)
        while j < len(self.extra) and self.extra[j] <= hi:
            if self.overlay[self.extra[j]] is not None:
                after.append(self.extra[j])
            j += 1
        f = self._floor(lo)
        ids = ([f] if f is not None and f <= hi else []) + sorted(after)
        return [(self.key(i), self.get(i)) for i in ids]
