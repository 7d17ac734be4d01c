"""The one request generator: a configuration file plus a mix file, and a
seed, give the records to load and the requests to send.

Everything is drawn with numpy from ``--seed``; nothing here imports the
store.  A mix file (``bench/traffic/<mix>.json``) holds only parameters:

    loop           the module under bench/traffic/ that sends the requests
                   and times them (``open``: arrivals at ``rate`` ops/s, due
                   times fixed before the run); a new arrival process is a
                   new module there, named by a new mix
    ops            share of each op kind: get, scan, put, update, delete
    scan_items     [least, most] items a SCAN asks for, uniform
    rate           offered ops/s, for loops that offer load at a rate

Keys are record ids written as 8-byte big-endian integers.  The
configuration loads the even ids ``0, 2, ..., 2(N-1)`` in a random order;
odd ids are free for PUTs of new keys.  GET and SCAN starts, UPDATEs and
DELETEs pick a loaded id by the configuration's request distribution
("uniform", or "zipfian" over ranks, scrambled through a seeded
permutation of the loaded ids as YCSB's ScrambledZipfianGenerator spreads
hot keys).  A PUT takes the odd id of a pair no earlier PUT took.  UPDATE
writes whether or not the key is present (the store's update is an
upsert).
"""
from __future__ import annotations

import dataclasses

import numpy as np

KINDS = ("get", "scan", "put", "update", "delete")
GET, SCAN, PUT, UPDATE, DELETE = range(len(KINDS))
WRITES = (PUT, UPDATE, DELETE)


def zipf_sampler(n: int, theta: float, rng: np.random.Generator):
    """Bounded zipfian ranks over [0, n) (YCSB's request distribution).
    Copied from ``benchmarks/common.py:zipf_sampler``, drawing from the
    caller's generator."""
    w = 1.0 / np.power(np.arange(1, n + 1), theta)
    cdf = np.cumsum(w / w.sum())

    def sample(k: int) -> np.ndarray:
        idx = np.searchsorted(cdf, rng.random(k)).astype(np.int64)
        return np.minimum(idx, n - 1)
    return sample


@dataclasses.dataclass
class Records:
    """What the load puts: ``order[j]`` is the j-th record put; record i
    has id ``2 i`` and value ``values[i]``."""
    n: int
    order: np.ndarray          # [n] int64, a permutation of range(n)
    values: np.ndarray         # [n, value_bytes] uint8


@dataclasses.dataclass
class Requests:
    """A request stream, one entry per request, in sending order."""
    kind: np.ndarray           # [m] uint8, index into KINDS
    key: np.ndarray            # [m] int64 id (GET key, SCAN start, write key)
    hi: np.ndarray             # [m] int64 SCAN end id (else = key)
    items: np.ndarray          # [m] int32 SCAN items asked for (else 1)
    value: np.ndarray          # [m, value_bytes] uint8 (writes; else 0)
    due: np.ndarray | None     # [m] float64 seconds from window start (open)

    def __len__(self) -> int:
        return len(self.kind)

    def slice(self, lo: int, hi: int) -> "Requests":
        return Requests(self.kind[lo:hi], self.key[lo:hi], self.hi[lo:hi],
                        self.items[lo:hi], self.value[lo:hi],
                        None if self.due is None else self.due[lo:hi])


class Generator:
    """Records and requests of one configuration under one mix."""

    def __init__(self, config: dict, mix: dict, seed: int,
                 records: int | None = None):
        self.config = config
        self.mix = mix
        self.n = int(records or config["recordcount"])
        self.value_bytes = int(config["value_bytes"])
        # one stream for the data, one for the requests: a mix change
        # leaves the loaded data as it was
        data_ss, req_ss = np.random.SeedSequence(seed).spawn(2)
        self._data_rng = np.random.default_rng(data_ss)
        self._rng = np.random.default_rng(req_ss)
        dist = config["requestdistribution"]
        if dist == "zipfian":
            self._ranks = zipf_sampler(self.n, float(config["zipfian_constant"]),
                                       self._rng)
            self._scramble = (self._rng.permutation(self.n)
                              if config.get("scrambled", True) else None)
        elif dist == "uniform":
            self._ranks, self._scramble = None, None
        else:
            raise ValueError(f"unknown requestdistribution {dist!r}")
        # PUTs of new keys take the odd id of pairs in this order
        self._fresh = self._rng.permutation(self.n)
        self._next_fresh = 0
        shares = np.array([float(mix["ops"].get(k, 0.0)) for k in KINDS])
        if shares.min() < 0 or abs(shares.sum() - 1.0) > 1e-9:
            raise ValueError(f"op shares must sum to 1: {mix['ops']}")
        self._shares = shares
        self._scan_items = tuple(mix.get("scan_items", (1, 1)))

    # ------------------------------------------------------------ records
    def records(self) -> Records:
        rng = self._data_rng
        return Records(
            n=self.n, order=rng.permutation(self.n).astype(np.int64),
            values=rng.integers(0, 256, (self.n, self.value_bytes),
                                dtype=np.uint8))

    # ----------------------------------------------------------- requests
    def _loaded_ids(self, m: int) -> np.ndarray:
        """``m`` loaded ids by the configuration's request distribution."""
        if self._ranks is None:
            idx = self._rng.integers(0, self.n, m)
        else:
            idx = self._ranks(m)
            if self._scramble is not None:
                idx = self._scramble[idx]
        return 2 * idx.astype(np.int64)

    def _fresh_odd_ids(self, m: int) -> np.ndarray:
        lo = self._next_fresh
        if lo + m > self.n:
            raise ValueError("the mix inserts more keys than the "
                             "configuration has free ids")
        self._next_fresh += m
        return 2 * self._fresh[lo:lo + m].astype(np.int64) + 1

    def requests(self, m: int, due: np.ndarray | None = None) -> Requests:
        """The next ``m`` requests of the mix (``due`` their send times)."""
        rng = self._rng
        kind = rng.choice(len(KINDS), size=m, p=self._shares).astype(np.uint8)
        key = self._loaded_ids(m)
        puts = kind == PUT
        key[puts] = self._fresh_odd_ids(int(puts.sum()))
        lo_items, hi_items = self._scan_items
        items = np.ones(m, np.int32)
        scans = kind == SCAN
        items[scans] = rng.integers(lo_items, hi_items + 1, int(scans.sum()))
        hi = key + 2 * (items.astype(np.int64) - 1)
        value = np.zeros((m, self.value_bytes), np.uint8)
        writes = (kind == PUT) | (kind == UPDATE)
        value[writes] = rng.integers(0, 256, (int(writes.sum()),
                                              self.value_bytes), dtype=np.uint8)
        return Requests(kind, key, hi, items, value, due)

    def writes(self, m: int, hot: int = 0, repeats: int = 1) -> Requests:
        """``m`` writes by the mix's shares of PUT, UPDATE and DELETE, then
        ``hot`` loaded keys updated ``repeats`` times each (set-up's write
        epochs, bench/harness.py ``warm_writes``)."""
        rng = self._rng
        shares = self._shares[list(WRITES)]
        kind = np.asarray(WRITES, np.uint8)[
            rng.choice(len(WRITES), size=m, p=shares / shares.sum())]
        key = self._loaded_ids(m)
        puts = kind == PUT
        key[puts] = self._fresh_odd_ids(int(puts.sum()))
        hot_keys = np.repeat(self._loaded_ids(hot), repeats)
        kind = np.concatenate([kind, np.full(len(hot_keys), UPDATE, np.uint8)])
        key = np.concatenate([key, hot_keys])
        n = len(kind)
        value = rng.integers(0, 256, (n, self.value_bytes), dtype=np.uint8)
        value[kind == DELETE] = 0
        return Requests(kind, key, key.copy(), np.ones(n, np.int32), value,
                        None)

    def open_arrivals(self, seconds: float, rate: float | None = None
                      ) -> Requests:
        """Open loop: ``round(rate * seconds)`` requests with due times
        drawn uniformly over [0, seconds) and sorted — Poisson arrivals
        with the count fixed, so every seed offers the same work."""
        rate = float(self.mix["rate"] if rate is None else rate)
        m = max(1, int(round(rate * seconds)))
        due = np.sort(self._rng.random(m)) * seconds
        return self.requests(m, due)


def key_bytes(ids: np.ndarray, width: int = 8) -> list[bytes]:
    """Ids as fixed-width big-endian keys (sorting numerically)."""
    buf = np.asarray(ids, dtype=f">u{width}").tobytes()
    return [buf[i:i + width] for i in range(0, len(buf), width)]
