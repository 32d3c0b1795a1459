"""Ouro-2.6B whole (``ouro_2_6b.think_backlog``): its configuration's file,
the counts by hand, the plain reference against the program's pieces, and
the cell at the tests' size through ``InferenceEngine`` on the CPU (the
kernels run through Pallas' interpreter) — a bucketed prefill, then decode
through every (loop step, layer) plane of the paged cache, against the
reference's full forward: logits not tokens, the exit masses beside them,
and the planted faults the limit has to catch."""

import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import common, models, serve
from benchmarks.reference import ops

from . import _tiny_ouro

# float32 program at the tiny size: the served token is the reference's
# best, so sound runs read 0; the limit leaves room for a float32 near-tie
# (two logits within the 1e-5 or so that float32 sums taken in another
# order differ by, at a logit spread of 3)
LIMITS = {"served_logit_gap": 1e-3}
CELL = "ouro_2_6b.think_backlog"
MM = ops.MATMULS["float32"]
LAYERS = _tiny_ouro.MODEL["num_hidden_layers"]
STEPS = _tiny_ouro.MODEL["total_ut_steps"]


@pytest.fixture(scope="module")
def devices():
    return jax.devices()[:1]


@pytest.fixture(scope="module")
def cell():
    return common.load_cell(CELL)


@pytest.fixture(scope="module")
def tiny():
    model, ref = models.load_with_reference("ouro")
    return model, ref, model.init_params(_tiny_ouro.MODEL, 5)


# -- the configuration's file ------------------------------------------------

PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-6, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152}


def test_configuration_is_the_published_model_whole(cell):
    cfg = cell["config"]
    mc = cfg["model_config"]
    for key, value in PUBLISHED.items():
        assert cfg[key] == value and mc[key] == value, key
    # nothing is cut: every layer, every loop step, the whole vocabulary
    assert cfg["reduced"] == []
    assert set(mc) - set(PUBLISHED) == {"initializer_range",
                                        "weights_dtype"}
    for key in ("sandwich_norms", "final_norm_between_steps", "exit_gate"):
        assert "Moves no cost" in cfg["assumed"][key]
        assert len(cfg["assumed"][key]) > 100
    for key in ("initializer_range", "weights", "serving"):
        assert key in cfg["assumed"]
    assert "replicas" in cfg["deployment"]
    assert "1,572,864" in cfg["bytes"] and "5.34 GB" in cfg["bytes"]
    icfg = cfg["engine"]["inference"]
    assert icfg == {
        "kv_block_size": 64, "kv_blocks": 81, "max_batch_slots": 8,
        "max_seq_len": 640, "prefill_buckets": [128, 192, 256],
        "token_budget": 5120, "max_new_tokens": 384,
        "weights_dtype": "bfloat16"}
    # every slot's worst case and the null block
    assert icfg["kv_blocks"] == 8 * 640 // 64 + 1
    assert icfg["token_budget"] == 8 * 640
    from benchmarks.generators._requests import token_id_range
    assert token_id_range(mc) == 49152
    bench = json.load(open(common.ROOT + "/BENCHMARK.json"))
    entry, = [c for c in bench["configs"] if c["name"] == "ouro_2_6b"]
    assert entry["source"] == cfg["source"] and entry["reduced"] == []
    assert cell["chips"] == 1
    assert "serve_tokens_per_s" in cell["end_to_end"]
    assert {"loop_decode_attn_ms", "loop_prefill_attn_ms", "decode_roofline",
            "serve_hbm_peak_gb"} <= set(cell["per_layer"])


def test_the_traffic_is_the_recipe_of_its_lengths_file(cell):
    import random
    import statistics
    nd = statistics.NormalDist()

    def quantiles(median, sigma, lo, hi):
        return [int(round(min(max(median * math.exp(
            sigma * nd.inv_cdf((i + 0.5) / 64)), lo), hi)))
            for i in range(64)]

    prompts = quantiles(128, 0.40, 64, 256)
    answers = quantiles(256, 0.30, 128, 384)
    rng = random.Random(20260961)
    rng.shuffle(answers)
    pairs = [[p, a] for p, a in zip(prompts, answers)]
    rng.shuffle(pairs)
    traffic = cell["traffic"]
    assert traffic["pairs"] == pairs
    assert all(64 <= p <= 256 and 128 <= a <= 384 and p + a <= 640
               for p, a in pairs)
    assert traffic["generator"] == "closed_loop"
    assert (traffic["callers"], traffic["warmup_iterations"],
            traffic["trace_seconds"]) == (16, 8, 3.0)
    assert set(traffic["limits"]) == {"ouro_2_6b"}
    # a request's worst case fits a slot's 10 blocks whatever its bucket
    buckets = cell["config"]["engine"]["inference"]["prefill_buckets"]
    assert max(max(min(b for b in buckets if b >= p), p + a)
               for p, a in pairs) <= 640
    # ~2.2k tokens live in a step: 8 slots at the mean prompt and half
    # the mean answer
    live = 8 * (statistics.mean(prompts) + statistics.mean(answers) / 2)
    assert 2000 < live < 2300


class _EmptyPrograms:
    """A served model whose programs compute nothing: the engine's own
    step logic, scheduler and block pool then run a cell's schedule at the
    host's speed."""
    num_layers = 1

    class config:
        max_position_embeddings = 65536

    def serving(self):
        return self

    def cache_buffers(self, icfg):
        return {"k_cache": 128}

    def cache_groups(self, icfg):
        from deepspeed_tpu.inference.kv_cache import CacheGroup
        return [CacheGroup("kv", 1, self.cache_buffers(icfg))]

    def prepare_params(self, params):
        return params

    def build_prefill(self, icfg, bucket):
        def prefill(params, caches, ids, true_len, tables, next_tokens,
                    slot):
            return {"tokens": jnp.int32(1)}, caches, next_tokens
        return prefill

    def build_decode(self, icfg):
        def decode(params, caches, tables, ctx_lens, tokens):
            return {"tokens": tokens}, caches
        return decode


@pytest.mark.parametrize("decode_ms,prefill_ms", [
    (31.9, 30.0), (30.9, 22.0), (32.9, 40.0)])
def test_the_stored_order_puts_prefills_into_the_traced_seconds(
        cell, decode_ms, prefill_ms):
    """The cell's schedule — closed loop, 16 callers over 8 slots in
    progress, the stored order — through ``serve.Loop`` and the engine's
    own ``step`` with empty programs and a stepped clock (a decode 31.9 ms,
    a prefill ~30 ms: my chip runs, PR 35; and 3% / 30% either way): the
    traced last 3 s of a 30 s window hold at least four prefills, so
    ``prefill_device_ms`` and ``loop_prefill_attn_ms`` have something to
    read.  (With the shuffle's first seed the window's 26 finishes bunched
    and the last 3 s held none, on the chip as here.)"""
    from benchmarks import generators
    from deepspeed_tpu.inference import InferenceEngine
    cfg, traffic = cell["config"], cell["traffic"]
    icfg = dict(cfg["engine"]["inference"], weights_dtype="float32")
    engine = InferenceEngine(_EmptyPrograms(), {"w": jnp.zeros((1,))},
                             config={"steps_per_print": 10 ** 9,
                                     "inference": icfg})
    now, prefills, log = [0.0], [0], []
    step, enqueue = engine.step, engine._enqueue_prefill

    def counted(request):
        prefills[0] += 1
        return enqueue(request)

    def timed():
        prefills[0] = 0
        start = now[0]
        done = step()
        now[0] += 1e-3 * (decode_ms + prefill_ms * prefills[0])
        log.extend([start] * prefills[0])
        return done

    engine._enqueue_prefill, engine.step = counted, timed
    source = generators.load(traffic["generator"]).make(
        traffic, cfg["model_config"], 1, icfg["max_batch_slots"])
    loop = serve.Loop(engine, source, icfg["max_batch_slots"],
                      clock=lambda: now[0])
    loop.start()
    for _ in range(traffic["warmup_iterations"]):
        loop.step(record=False)
    opened = now[0]
    tokens, seconds, _ = serve.drive(loop, 30.0 - traffic["trace_seconds"])
    traced_from = now[0]
    serve.drive(loop, traffic["trace_seconds"])
    traced = [t for t in log if traced_from <= t]
    assert len(traced) >= 4
    window = [t for t in log if opened <= t]
    assert 24 <= len(window) <= 34
    # eight tokens a step, a prefill's time apart
    assert tokens / seconds == pytest.approx(
        8e3 / decode_ms * (1 - len(window) * prefill_ms / 30e3), rel=0.02)
    engine.close()


@pytest.mark.parametrize("mc", ["tiny", "cell"])
def test_parameter_tree_is_the_programs(mc, cell):
    mc = _tiny_ouro.MODEL if mc == "tiny" else cell["config"]["model_config"]
    model = models.load("ouro")
    program = model.build_program_model(mc, {})
    assert program.param_shapes() == model.param_shapes(mc)
    leaves = jax.tree_util.tree_leaves(
        model.param_shapes(mc), is_leaf=lambda x: isinstance(x, tuple))
    assert sum(math.prod(s) for s in leaves) == model.param_count(mc)
    serving = program.serving()
    assert serving.num_layers == mc["num_hidden_layers"]
    assert serving.config.cache_planes == model.cache_planes(mc)


def test_seeded_weights_come_in_the_serving_dtype():
    model = models.load("ouro")
    mc = dict(_tiny_ouro.MODEL, weights_dtype="bfloat16")
    a, b = model.init_params(mc, 7), model.init_params(mc, 7)
    other = model.init_params(mc, 2 ** 31 + 5)
    leaves = jax.tree_util.tree_leaves(a)
    assert all(leaf.dtype == jnp.bfloat16 for leaf in leaves)
    assert all(bool((x == y).all()) for x, y in zip(
        leaves, jax.tree_util.tree_leaves(b)))
    assert bool((a["embed"] != other["embed"]).any())
    assert not bool(a["exit_gate"]["bias"].any())
    assert bool(a["exit_gate"]["kernel"].any())
    layer = a["layers"]["layer_1"]
    for norm in ("norm_attn_in", "norm_attn_out", "norm_mlp_in",
                 "norm_mlp_out"):
        assert bool((layer[norm]["scale"] == 1).all())
    # the layers' weights are their own, not one tree repeated
    assert bool((layer["o"]["kernel"]
                 != a["layers"]["layer_0"]["o"]["kernel"]).any())


def test_counts_by_hand(cell):
    mc = cell["config"]["model_config"]
    model = models.load("ouro")
    h = 2048
    layer = 4 * h * h + 3 * h * 5632 + 4 * h
    assert model.layer_params(mc) == layer == 51_388_416
    total = 48 * layer + 2 * 49152 * h + h + h + 1
    assert model.param_count(mc) == total == 2_667_974_657
    assert round(total / 1e6) == 2668 and round(2 * total / 1e7) == 534
    # a token leaves a K and a V row of 2048 bf16 values at 192 places
    assert model.cache_planes(mc) == 192
    assert model.cache_bytes_per_token(mc) == 192 * 2 * 2048 * 2 \
        == 1_572_864
    # the layers' weights FOUR times, the head, the final norm and the
    # gate once, and the live tokens' rows
    step = model.decode_bytes_per_step(mc, 2200)
    assert step == 2 * (4 * 48 * layer + 49152 * h + h + h + 1) \
        + 2200 * 1_572_864
    assert step == pytest.approx(23.4e9, rel=0.01)
    assert 2 * 4 * 48 * layer == pytest.approx(19.73e9, rel=1e-3)
    # one layer more: its weights four times more (and, with tokens
    # live, its four planes' rows)
    deeper = dict(mc, num_hidden_layers=49)
    assert model.decode_bytes_per_step(deeper, 0) \
        - model.decode_bytes_per_step(mc, 0) == 4 * layer * 2
    assert model.decode_bytes_per_step(deeper, 100) \
        - model.decode_bytes_per_step(mc, 100) \
        == 4 * layer * 2 + 100 * 4 * 2 * 2048 * 2
    # one loop step more: 48 planes more a live token (and the layers'
    # weights once more)
    longer = dict(mc, total_ut_steps=5)
    per_token = [model.decode_bytes_per_step(c, 1)
                 - model.decode_bytes_per_step(c, 0) for c in (mc, longer)]
    assert per_token[1] - per_token[0] == 48 * 2 * 2048 * 2 == 393_216
    assert model.decode_bytes_per_step(longer, 0) \
        - model.decode_bytes_per_step(mc, 0) == 48 * layer * 2
    assert "four times" in model.decode_bytes_per_step.__doc__.lower() \
        or "TIMES" in model.decode_bytes_per_step.__doc__


# -- the reference against the program's pieces -------------------------------

def test_rotation_is_the_programs(tiny):
    from deepspeed_tpu.models import ouro as program
    model, ref, _ = tiny
    mc = _tiny_ouro.MODEL
    config = model.build_program_model(mc, {}).config
    x = jax.random.normal(jax.random.PRNGKey(0), (24, 2, 128))
    np.testing.assert_allclose(
        np.asarray(program.rotate(x, jnp.arange(24), config)),
        np.asarray(ref.rotate(x, mc)), rtol=1e-5, atol=1e-5)
    # position 0 is not turned; the pairs are (c, c + 64)
    np.testing.assert_array_equal(np.asarray(ref.rotate(x, mc))[0],
                                  np.asarray(x)[0])
    one = jnp.zeros((2, 1, 128)).at[:, 0, 3].set(1.0)
    turned = np.asarray(ref.rotate(one, mc))[1, 0]
    assert set(np.nonzero(turned)[0]) == {3, 67}
    assert turned[3] == pytest.approx(math.cos(1e6 ** (-6 / 128)))


def test_the_references_exit_masses_are_the_programs_and_sum_to_one(tiny):
    from deepspeed_tpu.models import ouro as program
    _, ref, params = tiny
    h = jax.random.normal(jax.random.PRNGKey(2), (STEPS, 10, 256))
    masses = np.asarray(ref.masses(params, h))
    assert masses.shape == (STEPS, 10)
    np.testing.assert_allclose(masses.sum(axis=0), 1.0, rtol=1e-6)
    gate = params["exit_gate"]
    lam = jax.nn.sigmoid(h @ gate["kernel"][:, 0] + gate["bias"])
    np.testing.assert_allclose(
        masses, np.asarray(program.exit_masses(lam)), rtol=1e-5, atol=1e-6)
    # a gate that always fires puts everything on the first step
    sure = dict(params, exit_gate={"kernel": gate["kernel"] * 0,
                                   "bias": gate["bias"] + 50.0})
    np.testing.assert_allclose(np.asarray(ref.masses(sure, h))[0], 1.0)


def test_a_steps_keys_are_its_own_and_the_steps_share_the_weights(tiny):
    """The reference's four walks are walks over the SAME trees (one more
    step moves the logits; another step's weights do not exist), and the
    state of step r is the input of step r + 1."""
    model, ref, params = tiny
    mc = _tiny_ouro.MODEL
    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, 512, size=(1, 16)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        h3 = np.asarray(ref.states(params, ids, mc, MM))
        h2 = np.asarray(ref.states(params, ids,
                                   dict(mc, total_ut_steps=2), MM))
    assert h3.shape == (1, STEPS, 16, 256)
    np.testing.assert_allclose(h3[:, :2], h2, rtol=1e-5, atol=1e-5)
    assert np.abs(h3[:, 2] - h3[:, 1]).max() > 0.1
    # every state is normed: RMS 1 under a scale of ones
    np.testing.assert_allclose(np.sqrt((h3 ** 2).mean(-1)), 1.0, rtol=1e-4)


# -- prefill and decode through every plane -------------------------------------

def _serve(params, requests, model_config=None, watch=None):
    """Serve ``requests`` [(prompt, answer length)] on the tiny model and
    return each one's tokens; ``watch(engine)`` after every step."""
    from deepspeed_tpu.inference import InferenceEngine
    model = models.load("ouro")
    engine = InferenceEngine(
        model.build_program_model(model_config or _tiny_ouro.MODEL, {}),
        params, config=_tiny_ouro.ENGINE)
    rids = [engine.submit(p, max_new_tokens=n) for p, n in requests]
    while not engine.scheduler.idle() or engine._unread:
        engine.step()
        if watch is not None:
            watch(engine)
    results = {rid: engine.request(rid) for rid in rids}
    assert engine.allocator.free_blocks == engine.allocator.capacity
    tokens = [list(results[rid].generated) for rid in rids]
    engine.close()
    return tokens


def _positions(requests, served):
    """Padded ids and the (row, column) of the position that predicts each
    served token."""
    ids = np.zeros((len(requests), 96), np.int32)
    rows, cols = [], []
    for r, ((prompt, _), tokens) in enumerate(zip(requests, served)):
        n, new = len(prompt), len(tokens)
        ids[r, :n] = prompt
        ids[r, n:n + new] = tokens
        rows += [r] * new
        cols += list(range(n - 1, n + new - 1))
    return jnp.asarray(ids), jnp.asarray(rows), jnp.asarray(cols)


def _gaps(ref, params, requests, served):
    """How far each served token's logit lies below the reference's best."""
    ids, rows, cols = _positions(requests, served)
    logits = np.asarray(ref.position_logits(params, ids, rows, cols,
                                            _tiny_ouro.MODEL, MM))
    assert logits.std() > 0.5        # not a flat distribution
    tokens = np.concatenate([np.asarray(t) for t in served])
    return logits.max(axis=-1) - logits[np.arange(len(tokens)), tokens]


def _requests():
    rng = np.random.default_rng(35)
    # a prompt shorter than a page; one padded to its bucket (37 in 48);
    # one that fills its bucket; every answer crosses several pages
    return [(rng.integers(0, 512, size=n), new)
            for n, new in ((5, 40), (37, 40), (32, 36), (20, 40))]


def test_decode_through_every_plane_matches_the_full_forward(tiny):
    """A bucketed prefill, then decode through the 3 x 3 planes, four
    requests side by side: every served token's logit is the reference's
    best, by the reference's own full forward over the whole sequence."""
    _, ref, params = tiny
    requests = _requests()
    served = _serve(params, requests)
    assert [len(t) for t in served] == [n for _, n in requests]
    gaps = _gaps(ref, params, requests, served)
    assert len(gaps) == 156
    assert gaps.max() <= LIMITS["served_logit_gap"]


def test_the_reported_exit_masses_are_the_references(tiny):
    """One request alone: the decode at position ``n + j`` reports the exit
    distribution of that position, which the reference gives from its full
    forward; they agree to float32 rounding (1e-4: sums of 256 products in
    another order, through a sigmoid) and sum to 1."""
    _, ref, params = tiny
    rng = np.random.default_rng(8)
    prompt, new = rng.integers(0, 512, size=21), 24
    seen = []

    def watch(engine):
        counters = engine.model_counters
        if counters and (not seen or counters is not seen[-1][0]):
            seen.append((counters, [float(
                counters[f"exit_mass_step_{r + 1}"]) for r in range(STEPS)],
                float(counters["exit_step_mean"])))

    served, = _serve(params, [(prompt, new)], watch=watch)
    assert len(served) == new
    reported = np.asarray([masses for _, masses, _ in seen])
    # the decodes at positions n .. n + new - 2 (the last token is decoded
    # by no step)
    assert len(reported) >= new - 1
    reported = reported[:new - 1]
    ids = np.zeros((1, 96), np.int32)
    ids[0, :21], ids[0, 21:21 + new] = prompt, served
    cols = jnp.arange(21, 21 + new - 1)
    expected = np.asarray(ref.exit_mass(
        params, jnp.asarray(ids), jnp.zeros(new - 1, jnp.int32), cols,
        _tiny_ouro.MODEL, MM))
    assert expected.shape == (new - 1, STEPS)
    np.testing.assert_allclose(expected.sum(axis=1), 1.0, rtol=1e-6)
    np.testing.assert_allclose(reported, expected, atol=1e-4)
    means = np.asarray([mean for *_, mean in seen])[:new - 1]
    np.testing.assert_allclose(
        means, expected @ np.arange(1.0, STEPS + 1), atol=3e-4)
    # not one distribution at every position: the step most mass leaves
    # at differs along the answer
    assert expected.std(axis=0).max() > 0.05
    assert len(set(expected.argmax(axis=1))) > 1


# -- planted faults ---------------------------------------------------------------

def _one_step_fewer(monkeypatch):
    return dict(_tiny_ouro.MODEL, total_ut_steps=STEPS - 1)


def _reads(monkeypatch, plane_read):
    """Decode reads plane ``plane_read(plane)`` for plane ``plane``."""
    from deepspeed_tpu.models import ouro as program
    real = program.paged_decode_attention
    monkeypatch.setattr(
        program, "paged_decode_attention",
        lambda *a, layer, **kw: real(*a, layer=plane_read(layer), **kw))


def _the_step_before(monkeypatch):
    _reads(monkeypatch,
           lambda plane: jnp.where(plane >= LAYERS, plane - LAYERS, plane))


def _off_by_one_layer(monkeypatch):
    _reads(monkeypatch, lambda plane: (plane + 1) % (STEPS * LAYERS))


def _layer_with(monkeypatch, change):
    """``_layer`` called with ``change(lp, plane) -> (lp, plane)``."""
    from deepspeed_tpu.models import ouro as program
    real = program.OuroServing._layer

    def layer(self, lp, x, caches, positions, plane, attend):
        lp, plane = change(lp, plane)
        return real(self, lp, x, caches, positions, plane, attend)

    monkeypatch.setattr(program.OuroServing, "_layer", layer)


def _norm_that_skips_a_missing_scale(monkeypatch):
    from deepspeed_tpu.models import ouro as program
    real = program.rms_norm
    monkeypatch.setattr(program, "rms_norm", lambda p, x, eps: (
        x if p["scale"] is None else real(p, x, eps)))
    return real


def _last_steps_planes_for_all(monkeypatch):
    # one set of planes: every step writes and reads the last step's
    _layer_with(monkeypatch, lambda lp, plane: (
        lp, plane % LAYERS + (STEPS - 1) * LAYERS))


def _skip_an_output_norm(monkeypatch):
    _norm_that_skips_a_missing_scale(monkeypatch)
    _layer_with(monkeypatch, lambda lp, plane: (
        dict(lp, norm_mlp_out={"scale": None}), plane))


def _final_norm_before_the_head_only(monkeypatch):
    from deepspeed_tpu.models import ouro as program
    norm = _norm_that_skips_a_missing_scale(monkeypatch)
    real = program.OuroServing._ut_loop

    def ut_loop(self, params, x, caches, positions, attend):
        h, caches, gates = real(
            self, dict(params, final_norm={"scale": None}), x, caches,
            positions, attend)
        return (norm(params["final_norm"], h, self.config.rms_norm_eps),
                caches, gates)

    monkeypatch.setattr(program.OuroServing, "_ut_loop", ut_loop)


def _skip_rotation(monkeypatch):
    from deepspeed_tpu.models import ouro as program
    monkeypatch.setattr(program, "rotate", lambda x, positions, c: x)


FAULTS = {
    "one_loop_step_fewer": _one_step_fewer,
    "step_r_reads_step_r_minus_1s_planes": _the_step_before,
    "last_steps_planes_reused_for_all": _last_steps_planes_for_all,
    "a_sublayers_output_norm_skipped": _skip_an_output_norm,
    "final_norm_not_applied_between_steps": _final_norm_before_the_head_only,
    "rotation_skipped": _skip_rotation,
    "plane_index_off_by_one_layer": _off_by_one_layer}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_faults_fail_the_limit(tiny, fault, monkeypatch):
    """A program that runs a loop step fewer, reads another step's or
    another layer's planes, keeps one set of planes for every step, skips a
    sandwich norm, the final norm between steps or the rotation serves
    tokens whose logits lie below the reference's best by far more than the
    limit."""
    _, ref, params = tiny
    program_config = FAULTS[fault](monkeypatch)
    requests = _requests()
    served = _serve(params, requests, program_config)
    gaps = _gaps(ref, params, requests, served)
    assert gaps.max() > 100 * LIMITS["served_logit_gap"]


# -- the cell at the tests' size through the harness ---------------------------------

def test_the_harness_serves_the_loop_and_judges_every_position(devices):
    spec = _tiny_ouro.serve_spec(LIMITS)
    loop = serve.setup(spec, 3, devices)
    engine = loop.engine
    group, = engine.cache_groups
    assert (group.name, group.layers) == ("kv", STEPS * LAYERS)
    assert [c.shape for c in engine._caches] == [(STEPS * LAYERS, 49, 8,
                                                  256)] * 2
    for _ in range(80):
        loop.step()
    assert engine.decode_iterations > 60
    counters = engine.model_counters
    assert float(counters["ut_steps"]) == STEPS
    assert float(counters["cache_planes"]) == STEPS * LAYERS
    assert 1.0 <= float(counters["exit_step_mean"]) <= STEPS
    sample = serve.sample_finished(loop.finished, 3, n=12)
    serve.free(loop)
    gaps = serve.reference_gaps(spec, 3, sample)
    assert len(gaps) > 50
    assert gaps.max() <= LIMITS["served_logit_gap"]
    # the float8 control moves tokens, and by more than the limit
    low = serve.reference_gaps(spec, 3, sample, "fp8")
    assert (low > 0).sum() > 0 and low.max() > 100 * LIMITS[
        "served_logit_gap"]
