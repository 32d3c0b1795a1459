"""The plain references against ``deepspeed_tpu/models`` at a tiny size on
the CPU (float32 on both sides, no dropout), and the parameter trees the
benchmark makes against the program's own ``init``.

Tolerances: both sides compute in float32 here, in another order of
operations (fused QKV vs the same, top-k gather vs the same, logsumexp in
one piece), so they agree to a few float32 roundings of values of order 1
to 10: 2e-5 relative on a loss, 2e-4 absolute on logits of order 1.  On
the chip the program runs in bfloat16 and the limits are wider; they are
set from readings (PERF.md section 2) and live in the traffic files."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import generators, models
from benchmarks.reference import bert as ref_bert
from benchmarks.reference import gpt2 as ref_gpt2
from benchmarks.reference import ops

from . import _tiny

NO_DROPOUT = {"hidden": 0.0, "attention": 0.0, "embedding": 0.0}


def _shapes(tree):
    return jax.tree_util.tree_map(lambda x: tuple(x.shape), tree)


@pytest.mark.parametrize("name,cfg,traffic", [
    ("bert", _tiny.BERT, {"predictions_per_seq": 10}),
    ("gpt2", _tiny.GPT2, {})])
def test_parameter_tree_is_the_programs(name, cfg, traffic):
    model = models.load(name)
    program = model.build_program_model(cfg, traffic)
    theirs = jax.eval_shape(program.init, jax.random.PRNGKey(0))
    ours = model.init_params(cfg, 2 ** 31 + 3)
    assert _shapes(ours) == _shapes(theirs)
    again = model.init_params(cfg, 2 ** 31 + 3)
    other = model.init_params(cfg, 4)
    leaves = jax.tree_util.tree_leaves
    assert all(np.array_equal(a, b) for a, b in zip(leaves(ours),
                                                    leaves(again)))
    table = (lambda t: t["bert"]["embeddings"]["word"] if name == "bert"
             else t["wte"])
    assert not np.array_equal(table(ours), table(other))
    assert float(jnp.std(table(ours))) == pytest.approx(0.02, rel=0.1)


def test_bert_loss_matches_the_programs_model():
    traffic = {"seq_len": 64, "predictions_per_seq": 10, "pool": 1}
    model = models.load("bert")
    params = model.init_params(_tiny.BERT, 11)
    batch = generators.load("mlm_batches").make(traffic, _tiny.BERT, 11,
                                                8)[0]
    batch["attention_mask"][:, 50:] = 0      # some padding to mask
    program = model.build_program_model(_tiny.BERT, traffic)
    theirs = float(program.apply(
        params, {k: jnp.asarray(v) for k, v in batch.items()}, train=False))
    ours = float(ref_bert.block_loss(
        params, {k: jnp.asarray(v) for k, v in batch.items()}, _tiny.BERT,
        traffic, None, NO_DROPOUT, ops.matmul, ref_bert.batch_totals(batch)))
    assert ours == pytest.approx(theirs, rel=2e-5)
    # in blocks of rows the shares add up to the same loss
    totals = ref_bert.batch_totals(batch)
    parts = sum(float(ref_bert.block_loss(
        params, {k: jnp.asarray(v[lo:lo + 4]) for k, v in batch.items()},
        _tiny.BERT, traffic, None, NO_DROPOUT, ops.matmul, totals))
        for lo in (0, 4))
    assert parts == pytest.approx(ours, rel=1e-5)


def test_gpt2_logits_and_loss_match_the_programs_model():
    model = models.load("gpt2")
    params = model.init_params(_tiny.GPT2, 12)
    ids = np.random.default_rng(12).integers(0, 512, size=(3, 48),
                                             dtype=np.int32)
    program = model.build_program_model(_tiny.GPT2, {})
    theirs = np.asarray(program.logits(params, jnp.asarray(ids)))
    rows, cols = np.meshgrid(np.arange(3), np.arange(48), indexing="ij")
    ours = np.asarray(ref_gpt2.position_logits(
        params, jnp.asarray(ids), rows.reshape(-1), cols.reshape(-1),
        _tiny.GPT2, ops.matmul)).reshape(3, 48, -1)
    assert np.abs(ours - theirs).max() < 2e-4
    labels = np.concatenate([ids[:, 1:], np.full((3, 1), -100, np.int32)],
                            axis=1)
    loss_theirs = float(program.apply(
        params, {"input_ids": jnp.asarray(ids),
                 "labels": jnp.asarray(labels)}, train=False))
    batch = {"input_ids": ids}
    loss_ours = float(ref_gpt2.block_loss(
        params, {"input_ids": jnp.asarray(ids)}, _tiny.GPT2, {}, None,
        NO_DROPOUT, ops.matmul, ref_gpt2.batch_totals(batch)))
    assert loss_ours == pytest.approx(loss_theirs, rel=2e-5)


def test_reference_dropout_is_its_own_and_seeded():
    traffic = {"seq_len": 64, "predictions_per_seq": 10, "pool": 1}
    params = models.load("bert").init_params(_tiny.BERT, 13)
    batch = {k: jnp.asarray(v) for k, v in generators.load(
        "mlm_batches").make(traffic, _tiny.BERT, 13, 8)[0].items()}
    rates = {"hidden": 0.1, "attention": 0.1}
    totals = {"labels": 80.0, "rows": 8.0}

    def loss(key):
        return float(ref_bert.block_loss(params, batch, _tiny.BERT, traffic,
                                         key, rates, ops.matmul, totals))

    a, b, c = (loss(jax.random.PRNGKey(1)), loss(jax.random.PRNGKey(1)),
               loss(jax.random.PRNGKey(2)))
    assert a == b and a != c and abs(a - c) < 0.05 * a
