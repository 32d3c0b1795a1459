"""Open loop: requests arrive on a schedule fixed by the seed whether or
not earlier ones have finished — Poisson arrivals at ``rate_per_s``, or
bursts of ``burst`` requests every ``burst / rate_per_s`` seconds.  A
request's latency is timed from when it was due."""

import numpy as np

from ._requests import RequestList


class Source:
    open_loop = True

    def __init__(self, traffic, model_cfg, seed, slots):
        self.requests = RequestList(traffic, model_cfg, seed)
        rate, burst = float(traffic["rate_per_s"]), int(traffic.get(
            "burst", 1))
        horizon = float(traffic["horizon_s"])
        rng = np.random.default_rng([int(seed), 2])
        if burst > 1:
            starts = np.arange(0.0, horizon, burst / rate)
            times = np.repeat(starts, burst)
        else:
            n = int(rate * horizon * 1.5) + 16
            times = np.cumsum(rng.exponential(1.0 / rate, size=n))
            times = times[times < horizon]
        self.times = [float(t) for t in times]
        self.sent = 0

    def initial(self):
        return []

    def on_finish(self, n_finished):
        return []

    def due(self, now_s):
        """[(due time, prompt, answer)] for requests due by ``now_s``."""
        out = []
        while self.sent < len(self.times) and self.times[self.sent] <= now_s:
            prompt, answer = self.requests.request(self.sent)
            out.append((self.times[self.sent], prompt, answer))
            self.sent += 1
        return out


def make(traffic, model_cfg, seed, slots):
    return Source(traffic, model_cfg, seed, slots)
