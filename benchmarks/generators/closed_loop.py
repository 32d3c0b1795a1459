"""Closed loop: ``callers`` callers, each sending its next request the
moment its last one completes, so the queue is never empty and admissions
happen exactly when a slot frees.  With answers of fixed length the
sequence of prefill and decode programs follows from the request list, not
from the clock.

The slots start "in progress": the first ``slots`` requests are cut to
(j + 1/2) / slots of their answers, j = 0 .. slots-1, the remaining lives
of requests met at a random moment of a long run — so the window opens on
slots that finish one after another, as in steady state, without a warm-up
as long as the longest answer."""

from ._requests import RequestList


class Source:
    open_loop = False

    def __init__(self, traffic, model_cfg, seed, slots):
        self.requests = RequestList(traffic, model_cfg, seed)
        self.callers = int(traffic["callers"])
        self.slots = int(slots)
        self.sent = 0

    def _next(self):
        index = self.sent
        answer = None
        if index < self.slots:
            full = self.requests.lengths(index)[1]
            answer = max(2, round(full * (index + 0.5) / self.slots))
        self.sent += 1
        return self.requests.request(index, answer)

    def initial(self):
        return [self._next() for _ in range(self.callers)]

    def on_finish(self, n_finished):
        return [self._next() for _ in range(n_finished)]

    def due(self, now_s):
        return []


def make(traffic, model_cfg, seed, slots):
    return Source(traffic, model_cfg, seed, slots)
