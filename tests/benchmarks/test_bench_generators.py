"""Traffic generators: the same work for every seed, the same inputs for
the same seed."""

from collections import Counter

import numpy as np
import pytest

from benchmarks import common, generators

MODEL = {"vocab_size": 1000}


_traffic = common.load_traffic


def _drain(source, n):
    out = source.initial()
    while len(out) < n:
        out += source.on_finish(1)
    return out[:n]


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 11])
def test_closed_loop_same_multiset_for_every_seed(seed):
    traffic = _traffic("backlog")
    gen = generators.load("closed_loop")
    n = len(traffic["pairs"])
    # the first 16 are cut short (slots start in progress): count the
    # second round of the list, which is whole
    got = _drain(gen.make(traffic, MODEL, seed, 16), 2 * n)[n:]
    assert Counter((len(p), a) for p, a in got) == Counter(
        tuple(p) for p in traffic["pairs"])


def test_closed_loop_same_list_for_same_seed_and_same_lengths_for_all():
    traffic = _traffic("backlog")
    gen = generators.load("closed_loop")
    a = _drain(gen.make(traffic, MODEL, 5, 16), 80)
    b = _drain(gen.make(traffic, MODEL, 5, 16), 80)
    c = _drain(gen.make(traffic, MODEL, 6, 16), 80)
    assert all(np.array_equal(x[0], y[0]) and x[1] == y[1]
               for x, y in zip(a, b))
    # another seed: the same lengths in the same order, other token ids
    assert [(len(p), n) for p, n in a] == [(len(p), n) for p, n in c]
    assert not np.array_equal(a[0][0], c[0][0])


def test_token_ids_are_drawn_below_the_published_vocabulary():
    padded = {"vocab_size": 1024, "published_vocab_size": 1000}
    source = generators.load("closed_loop").make(
        _traffic("backlog"), padded, 3, 16)
    ids = np.concatenate([p for p, _ in _drain(source, 64)])
    assert 990 < ids.max() < 1000
    batch = generators.load("mlm_batches").make(
        dict(_traffic("seq128"), pool=1), padded, 3, 8)[0]
    assert 990 < batch["input_ids"].max() < 1000


def test_closed_loop_starts_in_progress():
    traffic = _traffic("backlog")
    source = generators.load("closed_loop").make(traffic, MODEL, 3, 16)
    first = source.initial()
    assert len(first) == traffic["callers"]
    full = [source.requests.lengths(i)[1] for i in range(len(first))]
    cut = [a for _, a in first]
    # the 16 that fill the slots are part-way through, the queued are whole
    assert all(2 <= c <= f for c, f in zip(cut[:16], full[:16]))
    assert cut[16:] == full[16:]
    assert sum(cut[:16]) < 0.7 * sum(full[:16])


def test_open_loop_schedule_is_the_seeds():
    traffic = _traffic("chat_open")
    gen = generators.load("open_loop")
    a, b = (gen.make(traffic, MODEL, 9, 16) for _ in range(2))
    assert a.times == b.times and a.times == sorted(a.times)
    rate = len(a.times) / traffic["horizon_s"]
    assert 0.7 * traffic["rate_per_s"] < rate < 1.3 * traffic["rate_per_s"]
    due = a.due(10.0)
    assert [d[0] for d in due] == [t for t in a.times if t <= 10.0]
    assert a.due(10.0) == []          # each request is due once
    burst = gen.make(dict(traffic, burst=8), MODEL, 9, 16)
    assert burst.times[:8] == [0.0] * 8 and burst.times[8] == pytest.approx(
        8 / traffic["rate_per_s"])


@pytest.mark.parametrize("mix", ["seq512", "seq128"])
def test_mlm_batches_label_exactly_n_positions(mix):
    traffic = dict(_traffic(mix), pool=2)
    gen = generators.load("mlm_batches")
    pool = gen.make(traffic, {"vocab_size": 30528}, 2 ** 32 + 5, 4)
    again = gen.make(traffic, {"vocab_size": 30528}, 2 ** 32 + 5, 4)
    for batch, same in zip(pool, again):
        labels = batch["masked_lm_labels"]
        assert ((labels >= 0).sum(axis=1)
                == traffic["predictions_per_seq"]).all()
        picked = labels >= 0
        assert (labels[picked] == batch["input_ids"][picked]).all()
        assert all(np.array_equal(batch[k], same[k]) for k in batch)
    assert not np.array_equal(pool[0]["input_ids"], pool[1]["input_ids"])
    assert gen.tokens_per_step(traffic, 4) == 4 * traffic["seq_len"]
