"""``models/mellum.py`` through ``deepspeed_tpu.initialize`` and
``engine.train_batch`` / ``eval_batch`` against the plain reference
(``benchmarks/reference/mellum.py``) on seeded weights at a small size; the
flash kernels and the grouped products run through Pallas' interpreter.

The program here is in float32, so it and the reference differ by the
order of their sums alone (the online softmax by blocks, the grouped
product's tiles, XLA's reductions): a few 1e-6 of a leaf.  The limit of
1e-4 leaves that room and is far under any fault in the mathematics — the
planted ones of ``tests/benchmarks/test_bench_mellum.py`` read 0.08 and
more by their worst leaf."""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as deepspeed
from benchmarks.models import mellum as bench_model
from benchmarks.reference import mellum as reference
from benchmarks.reference import ops
from deepspeed_tpu.models import mellum
from deepspeed_tpu.models.layers import cross_entropy_with_logits
from deepspeed_tpu.parallel import make_mesh
from deepspeed_tpu.utils.logging import logger
from tests.benchmarks import _tiny_mellum

LIMIT = 1e-4
MODEL, ADAM = _tiny_mellum.MODEL, _tiny_mellum.ADAM
ROWS, SEQ, STEPS = 2, 128, 3


def _batches(seed):
    rng = np.random.default_rng(seed)
    return [{"input_ids": rng.integers(0, MODEL["vocab_size"],
                                       size=(ROWS, SEQ), dtype=np.int32)}
            for _ in range(STEPS)]


def _flat(tree):
    return np.concatenate([np.asarray(x, np.float64).reshape(-1)
                           for x in jax.tree_util.tree_leaves(tree)])


def _gap(got, want):
    """Per leaf: the largest difference against the leaf's largest value."""
    out = {}
    paths = bench_model._init.leaf_paths(bench_model.param_shapes(MODEL))
    for path, g, w in zip(paths, jax.tree_util.tree_leaves(got),
                          jax.tree_util.tree_leaves(want)):
        g, w = np.asarray(g, np.float64), np.asarray(w, np.float64)
        out[path] = np.abs(g - w).max() / max(np.abs(w).max(), 1e-30)
    return out


def _reference_steps(params, batches):
    """Loss, first gradient and the weights after each batch's Adam step,
    gradients added over blocks of one row as the harness does."""
    totals = [reference.batch_totals(b) for b in batches]
    grad = jax.jit(jax.value_and_grad(
        lambda p, block, total: reference.block_loss(
            p, block, MODEL, None, None, None, ops.matmul, total)))
    beta1, beta2 = ADAM["betas"]
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, first = [], None
    for t, (batch, total) in enumerate(zip(batches, totals), 1):
        loss, grads = 0.0, None
        for row in range(ROWS):
            part, g = grad(params, {"input_ids": batch["input_ids"][
                row:row + 1]}, total)
            loss += float(part)
            grads = g if grads is None else jax.tree_util.tree_map(
                jnp.add, grads, g)
        losses.append(loss)
        first = first or grads
        m = jax.tree_util.tree_map(
            lambda m, g: beta1 * m + (1 - beta1) * g, m, grads)
        v = jax.tree_util.tree_map(
            lambda v, g: beta2 * v + (1 - beta2) * g * g, v, grads)
        params = jax.tree_util.tree_map(
            lambda p, m, v: p - ADAM["lr"] * (m / (1 - beta1 ** t)) / (
                jnp.sqrt(v / (1 - beta2 ** t)) + ADAM["eps"]), params, m, v)
    return losses, first, params


def _next_ids(ids):
    """The labels ``Mellum`` makes of ids alone: the next id, none for the
    last position."""
    return np.concatenate(
        [ids[:, 1:], np.full((ids.shape[0], 1), -100, ids.dtype)], axis=1)


class _HeadLines(logging.Handler):
    """The ``chunked_lm_loss geometry:`` lines logged since the last
    :meth:`take`."""

    def __init__(self):
        super().__init__(logging.INFO)
        self.lines = []

    def emit(self, record):
        if "chunked_lm_loss geometry:" in record.getMessage():
            self.lines.append(record.getMessage().split("geometry: ")[1])

    def take(self):
        lines, self.lines = self.lines, []
        return lines


SEED = 7


@pytest.fixture(scope="module")
def reference_steps():
    return _reference_steps(bench_model.init_params(MODEL, SEED),
                            _batches(SEED))


@pytest.fixture(scope="module", params=[True, False],
                ids=["remat_routing_kept", "no_remat"])
def trained(request, reference_steps):
    """One engine through three steps, beside the reference's: with every
    layer recomputed on the way back but for what ``mellum.SAVED_NAMES``
    keeps (the attention kernels' outputs and the expert layer's routing:
    the routing kept is the routing recomputed), and with nothing
    recomputed."""
    assert MODEL["remat"] and set(mellum.expert_shard.SAVED_NAMES) < set(
        mellum.SAVED_NAMES)
    params = bench_model.init_params(MODEL, SEED)
    batches = _batches(SEED)
    engine, *_ = deepspeed.initialize(
        model=bench_model.build_program_model(
            dict(MODEL, remat=request.param), None),
        config={"train_batch_size": ROWS, "steps_per_print": 2,
                "optimizer": {"type": "Adam", "params": ADAM}},
        mesh=make_mesh({"data": 1}, devices=jax.devices()[:1]),
        model_parameters=params)
    logits = np.asarray(engine.eval_batch(
        {"input_ids": batches[0]["input_ids"]}))
    head_lines = _HeadLines()
    logger.addHandler(head_lines)
    eval_loss = float(engine.eval_batch(
        {"input_ids": batches[0]["input_ids"],
         "labels": _next_ids(batches[0]["input_ids"])}))
    evaluated = head_lines.take()
    losses, unflatten = [], None
    shapes = jax.tree_util.tree_map(lambda x: x.shape, params)
    leaves, treedef = jax.tree_util.tree_flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple))

    def tree_of(flat):
        out, at = [], 0
        for shape in leaves:
            n = int(np.prod(shape))
            out.append(np.asarray(flat[at:at + n]).reshape(shape))
            at += n
        return jax.tree_util.tree_unflatten(treedef, out)

    first = None
    for n, batch in enumerate(batches):
        losses.append(float(engine.train_batch(iter([batch]))))
        if n == 0:
            first = tree_of(engine.flat.gather_master_unpadded(
                engine.state["opt"].exp_avg) / (1 - ADAM["betas"][0]))
    master = tree_of(engine.flat.gather_master_unpadded(
        engine.state["master"]))
    stepped = head_lines.take()
    logger.removeHandler(head_lines)
    reports = {k: float(v) for k, v in jax.device_get(
        engine._last_reports).items()}
    engine.close()
    return {"program": (losses, first, master), "logits": logits,
            "head_lines": (stepped, evaluated), "eval_loss": eval_loss,
            "reports": reports, "batches": batches,
            "reference": reference_steps,
            "start": bench_model.init_params(MODEL, SEED)}


def test_losses_match_the_reference(trained):
    got, want = trained["program"][0], trained["reference"][0]
    assert len(got) == STEPS
    for g, w in zip(got, want):
        assert abs(g - w) / w < LIMIT
    assert got[-1] < got[0]     # three steps move the loss


def test_every_leafs_gradient_matches_the_reference(trained):
    gaps = _gap(trained["program"][1], trained["reference"][1])
    assert len(gaps) == 4 * 9 + 3
    assert max(gaps.values()) < LIMIT, max(gaps.items(), key=lambda x: x[1])


def test_three_adam_steps_match_the_reference(trained):
    start = trained["start"]
    delta = [jax.tree_util.tree_map(lambda a, b: np.asarray(a) - np.asarray(
        b), side[2], start) for side in (trained["program"],
                                         trained["reference"])]
    # Adam turns a gradient's sign into a step of lr: where a gradient is
    # all but zero against eps its sign is rounding, so the change is held
    # to the reference's by its norm, leaf by leaf
    for g, w in zip(jax.tree_util.tree_leaves(delta[0]),
                    jax.tree_util.tree_leaves(delta[1])):
        assert np.linalg.norm(g - w) <= 1e-3 * np.linalg.norm(w)
    assert np.linalg.norm(_flat(delta[1])) > 0


def test_eval_batch_returns_the_references_logits(trained):
    ids = trained["batches"][0]["input_ids"]
    rows, cols = np.meshgrid(np.arange(ROWS), np.arange(SEQ), indexing="ij")
    want = np.asarray(jax.jit(lambda p, b: reference.eval_logits(
        p, b, rows.reshape(-1), cols.reshape(-1), MODEL, ops.matmul))(
        trained["start"], {"input_ids": ids})).reshape(ROWS, SEQ, -1)
    assert trained["logits"].shape == want.shape
    assert np.abs(trained["logits"] - want).max() < LIMIT * np.abs(want).max()


def test_the_head_makes_its_gradient_in_the_forward(trained):
    """The step's program holds the head's three products a chunk in the
    forward's one loop and no other form of it; a labelled ``eval_batch``
    does no gradient work, and returns the loss of the logits an
    unlabelled one returns."""
    geometry = (f"rows={ROWS} seq={SEQ} chunk={MODEL['loss_chunk']} "
                f"chunks={SEQ // MODEL['loss_chunk']} "
                f"vocab={MODEL['vocab_size']} head_products_per_chunk=")
    stepped, evaluated = trained["head_lines"]
    assert stepped == [geometry + "3 (gradient in the forward)"]
    assert evaluated == [geometry + "1 (primal)"]
    want = float(cross_entropy_with_logits(
        trained["logits"], _next_ids(trained["batches"][0]["input_ids"])))
    assert trained["eval_loss"] == pytest.approx(want, rel=1e-5)


def test_the_step_reports_the_expert_layers_counters(trained):
    reports = trained["reports"]
    assert set(reports) == {
        "training/moe_expert_load_max_over_mean",
        "training/moe_local_assignment_share", "training/moe_pair_passes",
        "training/moe_tokens_without_local_expert", "training/moe_aux_loss",
        "training/moe_rows_moved_share"}
    # 2 of 8 experts held, 2 choices a token
    assert 0.1 < reports["training/moe_local_assignment_share"] < 0.45
    assert reports["training/moe_pair_passes"] >= 1
    assert reports["training/moe_expert_load_max_over_mean"] >= 1
    assert 1.9 < reports["training/moe_aux_loss"] < 4   # top_k when even
    # 256 tokens, 512 pairs, a pass of 256 rows: a layer moves the pass
    # three ways in and its held pairs' rows two ways out, of the
    # 3 * 256 + 2 * 512 rows of every pair's
    assert reports["training/moe_pair_passes"] == 1
    held = reports["training/moe_local_assignment_share"] * 512
    moved = reports["training/moe_rows_moved_share"]
    assert 0 < moved <= 1
    assert moved == pytest.approx((768 + 2 * held) / 1792, rel=1e-5)


def test_the_shares_add_up_to_the_uncut_layer():
    """The four quarters' expert parts, attention counted once, are the
    uncut 8-expert reference layer: what ties the share to the model."""
    whole = dict(MODEL, num_experts=8, first_expert=0)
    params = bench_model.init_params(whole, 11)["layers"]["layer_3"]
    x = jax.random.normal(jax.random.PRNGKey(3), (1, SEQ, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, _ = reference.layer(params, x[0], whole, ops.matmul,
                                  "full_attention")
    parts, attended = [], None
    for first in (0, 2, 4, 6):
        share = dict(MODEL, first_expert=first)
        model = bench_model.build_program_model(share, None)
        held = jax.tree_util.tree_map(lambda a: a, params)
        held["moe"] = dict(params["moe"], experts=jax.tree_util.tree_map(
            lambda a: a[first:first + 2], params["moe"]["experts"]))
        tables = {"full_attention": mellum.rotary_tables(
            model.config, "full_attention", SEQ)}
        if attended is None:
            attended = x + model._attention(held, x, "full_attention",
                                            tables)
        out, _, _ = model._layer(held, x, "full_attention", tables)
        parts.append(out - attended)
    got = attended + sum(parts)
    assert np.abs(np.asarray(got[0]) - np.asarray(want)).max() \
        < LIMIT * np.abs(np.asarray(want)).max()
    # and one share alone is not the layer
    assert np.abs(np.asarray((attended + parts[0])[0])
                  - np.asarray(want)).max() > 1e-2


def test_yarn_frequencies_by_hand():
    """The published full-attention rotary: 128-wide heads, theta 500000,
    factor 16 over 8192 positions.  The pair that turns 32 times over 8192
    positions is 128 ln(8192 / 64 pi) / (2 ln 500000) = 18.08 -> 18, the one
    that turns once 34.98 -> 35: pairs up to 18 keep their frequency, pairs
    from 35 on are slowed 16 times, pair 26 lies 8/17 of the way."""
    config = mellum.MellumConfig()
    got, factor = mellum.rotary_inv_freq(config, "full_attention")
    plain = 500000.0 ** (-np.arange(0, 128, 2) / 128)
    want = plain.copy()
    want[35:] /= 16
    for i in range(19, 35):
        r = (i - 18) / 17
        want[i] = plain[i] / 16 * r + plain[i] * (1 - r)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)
    assert want[26] == pytest.approx(
        plain[26] * (1 - 8 / 17 * 15 / 16), rel=1e-12)
    assert factor == pytest.approx(0.1 * np.log(16) + 1, rel=1e-12)
    # the reference's own, written apart from the program's
    ref, ref_factor = reference.inv_freq(
        {"rope_parameters": mellum.ROPE_PARAMETERS, "head_dim": 128},
        "full_attention")
    np.testing.assert_allclose(ref, want, rtol=1e-12)
    assert ref_factor == factor
    # sliding layers: the plain frequencies, no factor
    got, factor = mellum.rotary_inv_freq(config, "sliding_attention")
    np.testing.assert_allclose(np.asarray(got), plain, rtol=1e-6)
    assert factor == 1.0
