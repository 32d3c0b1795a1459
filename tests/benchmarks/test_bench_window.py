"""The serve loop's own counting on a stub engine with known iteration
times: tokens inside the window, gaps between a request's tokens, time to
first token, occupancy."""

from types import SimpleNamespace

import pytest

from benchmarks import serve


class Clock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


class StubEngine:
    """Admits from a FIFO queue into ``slots`` slots; a step costs 50 ms a
    prefill plus 10 ms for the decode, and emits one token per admitted
    request plus one per active slot — as ``InferenceEngine.step`` does."""

    def __init__(self, slots, clock):
        self.clock = clock
        self.queue, self.requests = [], {}
        self.scheduler = SimpleNamespace(slots=[None] * slots,
                                         queue_depth=0, active_count=0)
        self.generated_tokens = 0
        self._next = 0

    def submit(self, prompt, max_new_tokens):
        rid = f"r{self._next}"
        self._next += 1
        self.requests[rid] = SimpleNamespace(
            request_id=rid, prompt=list(prompt), generated=[],
            max_new_tokens=max_new_tokens, finish_reason=None,
            context_len=len(prompt))
        self.queue.append(self.requests[rid])
        self.scheduler.queue_depth = len(self.queue)
        return rid

    def request(self, rid):
        return self.requests[rid]

    def forget(self, rid):
        self.requests.pop(rid)

    def step(self):
        slots, done = self.scheduler.slots, []
        for i, r in enumerate(slots):
            if r is not None and len(r.generated) >= r.max_new_tokens:
                r.finish_reason = "max_new_tokens"
                done.append(r)
                slots[i] = None
        for i in range(len(slots)):
            if slots[i] is None and self.queue:
                slots[i] = self.queue.pop(0)
                slots[i].generated.append(1)          # the prefill's token
                self.generated_tokens += 1
                self.clock.now += 0.050
        active = [r for r in slots if r is not None
                  and len(r.generated) < r.max_new_tokens]
        for r in active:
            r.generated.append(2)
            self.generated_tokens += 1
        if active:
            self.clock.now += 0.010
        self.scheduler.queue_depth = len(self.queue)
        self.scheduler.active_count = sum(r is not None for r in slots)
        return done


class Backlog:
    """Two callers, answers of 5 tokens, one slot each."""
    open_loop = False

    def initial(self):
        return [([7] * 3, 5), ([7] * 3, 5)]

    def on_finish(self, n):
        return [([7] * 3, 5)] * n

    def due(self, now):
        return []


def test_window_counts_tokens_and_gaps_by_its_own_stamps():
    clock = Clock()
    engine = StubEngine(2, clock)
    loop = serve.Loop(engine, Backlog(), 2, clock=clock)
    loop.start()
    tokens, seconds, iterations = serve.drive(loop, 1.0)
    # a request: 1 prefill (50 ms) + 4 decode steps; both slots in step,
    # so a cycle is 2 prefills + 4 decodes + the sweep step = 0.14 s and
    # 10 tokens; the window closes on the first step that passes 1.0 s
    assert seconds == pytest.approx(1.0, abs=0.14)
    # counted by the loop's own stamps; the stub's counter agrees
    assert tokens == loop.tokens == engine.generated_tokens
    assert tokens / seconds == pytest.approx(10 / 0.14, rel=0.08)
    assert iterations == loop.iterations
    # every gap between a request's successive tokens is one decode step
    assert loop.gaps and all(g == pytest.approx(0.010) for g in loop.gaps)
    # time to first token: the second caller waits for the first's prefill
    assert loop.ttfts[0] == pytest.approx(0.110)   # 2 prefills + the decode
    assert max(loop.occupancy) == 1.0 and min(loop.occupancy) == 0.0
    assert len(loop.finished) >= 10
    assert all(len(t) == 5 for _, t in loop.finished)


def test_sample_of_finished_requests_holds_the_longest_and_is_seeded():
    finished = [([1] * n, [2] * (n % 7 + 1)) for n in range(3, 40)]
    a = serve.sample_finished(finished, 5)
    assert a == serve.sample_finished(finished, 5)
    assert a != serve.sample_finished(finished, 6)
    assert len(a) == serve.CHECK_REQUESTS
    longest = max(len(p) + len(t) for p, t in finished)
    assert len(a[0][0]) + len(a[0][1]) == longest
    assert serve.sample_finished([], 5) == []


def test_window_count_does_not_read_the_programs_counter():
    """A program whose own counter counts something else moves nothing."""
    clock = Clock()
    engine = StubEngine(2, clock)
    loop = serve.Loop(engine, Backlog(), 2, clock=clock)
    loop.start()
    tokens, seconds, _ = serve.drive(loop, 0.5)
    engine.generated_tokens = -10 ** 9
    more, _, _ = serve.drive(loop, 0.5)
    assert more == pytest.approx(tokens, abs=10)
    assert loop.tokens == tokens + more == sum(
        len(t) for _, t in loop.finished) + sum(
        len(r.generated) for r in engine.scheduler.slots if r is not None
        and r.request_id in loop.live)
