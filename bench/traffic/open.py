"""Open loop: requests arrive at due times fixed before the run, Poisson
arrivals at the mix's ``rate`` with the count fixed, so that every seed
offers the same work (``Generator.open_arrivals``).  Everything due is
submitted, then one drain.  A request's latency runs from its due time to
the end of the drain that answers it, so time queued behind a slow epoch
counts.

A loop module (``bench/traffic/<loop>.py``, named by a mix's ``loop``)
gives the harness ``Loop(session, seconds, rate)`` with:

    epoch_max            the most requests one drain of the window can hold
    warm_round()         one unmeasured round of the mix (set-up)
    run(on_window)       serve the window; its (start, end) on the host
                         clock; ``on_window()`` runs as the window opens
    window_requests(w0)  (latency in seconds, kind) of every request of
                         the window, given the window's start
    lateness()           how late each of the window's requests was sent,
                         in seconds, or None
"""
from __future__ import annotations

import contextlib
import time

import numpy as np

# arrivals served before the window opens, so that the queue is in its
# steady state when it does
LEADIN_SECONDS = 0.5
# after a host stall the next drain carries the stall's whole backlog: the
# largest drain set-up prepares for holds this many seconds of arrivals
BACKLOG_SECONDS = 2.0


def serve(client, reqs, ops: list, warm: float, seconds: float,
          on_window=lambda: None) -> tuple[float, float, np.ndarray]:
    """Arrivals at their due times: everything due is submitted, then one
    drain.  Requests due in [warm, warm + seconds) are measured; the ones
    before are the lead-in, and ``on_window()`` runs as the window opens.
    Returns the window's (start, end) on the host clock and how late each
    request was submitted."""
    due = reqs.due
    n = len(due)
    late = np.zeros(n)
    start = time.perf_counter()
    in_window = False
    window = contextlib.ExitStack()
    i = 0
    end = start + warm
    while i < n:
        now = time.perf_counter() - start
        if due[i] > now:
            with client.spans("wait"):
                time.sleep(due[i] - now)
            now = time.perf_counter() - start
        if now >= warm and not in_window:
            on_window()
            window.enter_context(client.spans("window"))
            in_window = True
        j = int(np.searchsorted(due, now, side="right"))
        late[i:j] = now - due[i:j]
        end = client.serve(reqs.slice(i, j), ops[i:j], due[j - 1] >= warm)
        i = j
    window.close()
    return start + warm, end, late


class Loop:
    def __init__(self, session, seconds: float, rate: float | None = None):
        self.session, self.seconds = session, seconds
        self.rate = float(session.mix["rate"] if rate is None else rate)
        self.epoch_max = max(1, int(self.rate * BACKLOG_SECONDS))
        self.warm = LEADIN_SECONDS
        self.reqs = session.gen.open_arrivals(self.warm + seconds, self.rate)
        self.ops = session.ops(self.reqs)
        self.late = None

    def warm_round(self) -> None:
        """One second of the mix's arrivals, all of it lead-in."""
        s = self.session
        reqs = s.gen.open_arrivals(1.0, self.rate)
        serve(s.client, reqs, s.ops(reqs), float("inf"), 0.0)

    def run(self, on_window=lambda: None) -> tuple[float, float]:
        win0, win1, self.late = serve(self.session.client, self.reqs,
                                      self.ops, self.warm, self.seconds,
                                      on_window)
        return win0, win1

    def window_requests(self, win0: float) -> tuple[np.ndarray, np.ndarray]:
        lat, kinds = [], []
        lo, hi = self.warm, self.warm + self.seconds
        for ep in self.session.client.epochs:
            if not ep.measured:
                continue
            d = ep.reqs.due
            keep = (d >= lo) & (d < hi)
            lat.append(ep.end - (win0 - lo + d[keep]))
            kinds.append(ep.reqs.kind[keep])
        if not lat:
            return np.zeros(0), np.zeros(0, np.uint8)
        return np.concatenate(lat), np.concatenate(kinds)

    def lateness(self) -> np.ndarray | None:
        if self.late is None:
            return None
        return self.late[self.reqs.due >= self.warm]
