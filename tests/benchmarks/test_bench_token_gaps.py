"""The gap between a request's tokens, twice: as the harness stamps it
from outside after each ``engine.step()`` (``serve.Loop``) and as the
program books it itself (``Request.step_times``, repaired by PR 24 to be
the gap since that request's previous token).  On one injected clock that
only the compiled programs move — a prefill 50 ms, a decode 10 ms — the
two agree token for token."""

import jax
import pytest

from benchmarks import serve

from . import _tiny


def costing(program, clock, seconds):
    def run(*args):
        clock[0] += seconds
        return program(*args)
    return run


@pytest.fixture(scope="module")
def served():
    """A tiny backlog cell driven for 80 steps: per request, its token
    count and the gaps the program booked; and the stamps the harness
    took of it, (tokens seen, clock)."""
    loop = serve.setup(_tiny.serve_spec({"served_logit_gap": 1.0}), 21,
                       jax.devices()[:1])
    engine, clock = loop.engine, [1000.0]
    loop.clock = engine._clock = lambda: clock[0]
    engine._decode = costing(engine._decode, clock, 0.010)
    for bucket in list(engine._prefills):
        engine._prefills[bucket] = costing(engine._prefills[bucket], clock,
                                           0.050)
    before = set(loop.live)      # stamped on the wall clock so far: left out
    requests, stamps = {}, {}

    def note_submitted():
        for rid, rec in loop.live.items():
            if rid not in before:
                requests.setdefault(rid, rec[0])

    for _ in range(80):
        note_submitted()         # the requests the last step's finishes sent
        loop.step()
        note_submitted()
        for rid, request in requests.items():
            rec, seen = loop.live.get(rid), stamps.setdefault(rid, [])
            if rec and rec[1] and (not seen or seen[-1][0] != rec[1]):
                seen.append((rec[1], rec[2]))
    # as they stand now: close() drains the requests still in their slots
    booked = {rid: (len(r.generated), list(r.step_times))
              for rid, r in requests.items() if r.generated}
    serve.free(loop)
    return booked, stamps


def test_programs_gaps_are_the_harnesss_token_for_token(served):
    served_requests, stamps = served
    compared = behind_a_prefill = 0
    for rid, (tokens, booked) in served_requests.items():
        seen = stamps[rid]
        assert len(booked) == tokens - 1 == len(seen)
        for (n0, t0), (n1, t1) in zip(seen, seen[1:]):
            assert n1 == n0 + 1          # one token a step after the first
            # token n1 is the request's n1-th: its gap is booked n1 - 2
            assert booked[n1 - 2] == pytest.approx(t1 - t0, abs=1e-9)
            compared += 1
            behind_a_prefill += t1 - t0 > 0.055
    assert compared > 200
    # some tokens waited behind a neighbour's prefill, and both say so;
    # the decode call's own 10 ms would have hidden it
    assert behind_a_prefill > 5


def test_the_harnesss_list_leaves_out_only_the_token_of_the_prefills_step(
        served):
    """A request's second token comes in the ``step()`` of its first, so
    the harness has no stamp between them (PERF.md section 7); the program
    books that gap: 10 ms, the decode that followed its own prefill, or
    more where another request was admitted after it in that step."""
    served_requests, stamps = served
    first = [booked[0] for _, booked in served_requests.values()]
    for rid, (tokens, booked) in served_requests.items():
        assert len(booked) == len(stamps[rid]) - 1 + 1
        assert stamps[rid][0][0] == 2        # first stamp: two tokens
    assert min(first) == pytest.approx(0.010)
    assert all(t == pytest.approx(0.010 + 0.050 * round((t - 0.010) / 0.050))
               for t in first)
