"""Closed loop: the mix's ``clients`` each keep one request outstanding.
All of them send together, one drain answers them, and the next round is
sent when the last answer is back, as YCSB's client threads do with one
operation each in flight.  Every request of a round (an epoch) has the
epoch's latency: from the first submit to the end of its drain.

The interface the harness uses is the one ``open.py`` describes.
"""
from __future__ import annotations

import time

import numpy as np


class Loop:
    def __init__(self, session, seconds: float, rate: float | None = None):
        self.session, self.seconds = session, seconds
        self.epoch_max = int(session.mix["clients"])

    def _epoch(self, measured: bool) -> float:
        s = self.session
        reqs = s.gen.requests(self.epoch_max)
        return s.client.serve(reqs, s.ops(reqs), measured)

    def warm_round(self) -> None:
        """One unmeasured round of every client."""
        self._epoch(False)

    def run(self, on_window=lambda: None) -> tuple[float, float]:
        on_window()
        with self.session.client.spans("window"):
            start = end = time.perf_counter()
            while end - start < self.seconds:
                end = self._epoch(True)
        return start, end

    def window_requests(self, win0: float) -> tuple[np.ndarray, np.ndarray]:
        eps = [e for e in self.session.client.epochs if e.measured]
        if not eps:
            return np.zeros(0), np.zeros(0, np.uint8)
        return (np.concatenate([np.full(len(e.reqs), e.end - e.start)
                                for e in eps]),
                np.concatenate([e.reqs.kind for e in eps]))

    def lateness(self) -> None:
        """A closed loop sends each round when the last returns: never
        late."""
        return None
