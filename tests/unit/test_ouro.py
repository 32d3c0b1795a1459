"""The looped decoder (``models/ouro.py``): a model whose cache planes are
not its layers.  The cache it names and what the engine allocates for it,
the shape of its programs (the layers' body traced once, under one loop over
the steps), the donation of the carried caches, the exit gate's arithmetic
and the counters it reports.  The logits against the plain reference and the
planted faults are ``tests/benchmarks/test_bench_ouro.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import DeepSpeedInferenceConfig, InferenceEngine
from deepspeed_tpu.inference.kv_cache import cache_bytes
from deepspeed_tpu.models.ouro import (OuroConfig, OuroForServing,
                                       exit_masses)

LAYERS, STEPS = 3, 3

ENGINE = {"steps_per_print": 10 ** 9, "inference": {
    "kv_block_size": 8, "kv_blocks": 33, "max_batch_slots": 3,
    "max_seq_len": 64, "prefill_buckets": [16, 32], "token_budget": 192,
    "max_new_tokens": 16, "weights_dtype": "float32"}}


def tiny_model(**changes):
    return OuroForServing(OuroConfig(**{**dict(
        vocab_size=256, hidden_size=256, num_hidden_layers=LAYERS,
        num_attention_heads=2, num_key_value_heads=2, head_dim=128,
        intermediate_size=512, total_ut_steps=STEPS,
        max_position_embeddings=640), **changes}))


def seeded(model, seed=1):
    leaves, tree = jax.tree_util.tree_flatten(
        model.param_shapes(), is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        jnp.ones(shape) if len(shape) == 1
        else 0.2 * jax.random.normal(key, shape)
        for key, shape in zip(keys, leaves)])


@pytest.fixture(scope="module")
def served():
    model = tiny_model()
    return model, seeded(model)


def _programs(model):
    """The decode program and a prefill bucket's, with shapes to trace
    them on."""
    icfg = DeepSpeedInferenceConfig(ENGINE)
    serving = model.serving()
    shapes = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.float32), model.param_shapes(),
        is_leaf=lambda x: isinstance(x, tuple))
    group, = serving.cache_groups(icfg)
    caches = tuple(jax.ShapeDtypeStruct(
        (group.layers, 33, 8, row), jnp.float32)
        for row in group.buffers.values())
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    return {
        "decode": (serving.build_decode(icfg),
                   (shapes, caches, (ints(3, 8),), ints(3), ints(3))),
        "prefill": (serving.build_prefill(icfg, 32),
                    (shapes, caches, ints(1, 32), ints(), (ints(8),),
                     ints(3), ints()))}


# a ``fori_loop`` over a known number of steps is traced as a ``scan``;
# both lower to one HLO ``while``
LOOPS = ("while", "scan")


def _calls(jaxpr, name, inside_loop=False, found=None):
    """[(is it inside a loop)] of every ``name`` equation of a jaxpr,
    through every nested jaxpr."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            found.append(inside_loop)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _calls(sub, name, inside_loop or eqn.primitive.name in LOOPS,
                   found)
    return found


# -- the cache: planes, not layers ---------------------------------------------

def test_the_cache_is_one_group_of_steps_times_layers_planes():
    icfg = DeepSpeedInferenceConfig(ENGINE)
    serving = tiny_model().serving()
    group, = serving.cache_groups(icfg)
    assert (group.name, group.layers, group.pages) == ("kv", STEPS * LAYERS,
                                                       None)
    assert group.buffers == serving.cache_buffers(icfg) == {
        "k_cache": 256, "v_cache": 256}
    # the weights' count is not the planes'
    assert serving.num_layers == LAYERS
    assert serving.build_decode(icfg).__name__ == "decode"
    assert serving.build_prefill(icfg, 16).__name__ == "prefill"


def test_the_published_model_caches_192_planes_and_a_block_is_100_mb():
    """The cell's own numbers, from the objects the engine sizes by: 4 x 48
    planes, ``planes x 81 x 64 x 2048`` values a buffer, 1,572,864 B a
    token, 100.66 MB a block of 64 tokens, 8.15 GB in all — one block is
    1/80 of the pool."""
    icfg = DeepSpeedInferenceConfig({"inference": {
        "kv_block_size": 64, "kv_blocks": 81, "max_batch_slots": 8,
        "max_seq_len": 640, "prefill_buckets": [128, 192, 256],
        "token_budget": 5120, "max_new_tokens": 384,
        "weights_dtype": "bfloat16"}})
    serving = OuroForServing(OuroConfig()).serving()
    group, = serving.cache_groups(icfg)
    rows = tuple(group.buffers.values())
    assert (group.layers, group.num_blocks(icfg), icfg.kv_block_size,
            rows) == (192, 81, 64, (2048, 2048))
    assert cache_bytes(group.layers, 1, 1, rows, jnp.bfloat16) == 1_572_864
    assert cache_bytes(group.layers, 1, 64, rows, jnp.bfloat16) \
        == 100_663_296
    assert cache_bytes(group.layers, 81, 64, rows, jnp.bfloat16) \
        == 8_153_726_976
    assert group.table_width(icfg) == icfg.max_blocks_per_seq == 10
    serving.check_tpu_geometry(icfg)
    with pytest.raises(ValueError, match="cannot tile"):
        tiny_model(num_attention_heads=2, num_key_value_heads=2,
                   head_dim=32).serving().check_tpu_geometry(icfg)


def test_the_engine_allocates_the_planes_and_a_grant_is_the_same_blocks_in_each(
        served):
    model, params = served
    engine = InferenceEngine(model, params, config=ENGINE)
    assert [c.shape for c in engine._caches] == [(STEPS * LAYERS, 33, 8,
                                                  256)] * 2
    assert [t.shape for t in engine._tables] == [(3, 8)]
    assert engine.cache_block_bytes == {
        "k_cache": STEPS * LAYERS * 8 * 256 * 4,
        "v_cache": STEPS * LAYERS * 8 * 256 * 4}
    rng = np.random.default_rng(0)
    rid = engine.submit(rng.integers(0, 256, size=13), max_new_tokens=12)
    engine.step()
    request = engine.request(rid)
    grant, = request.grants
    # 13 + 12 tokens reserve 4 blocks (the bucket of 16 takes 2)
    assert len(grant) == 4
    engine.step()
    jax.block_until_ready(engine._caches)
    # every plane holds the prompt's rows in the request's first blocks and
    # nowhere else: one table row serves all the planes
    for cache in engine._caches:
        written = np.abs(np.asarray(cache)).sum(axis=(2, 3)) > 0
        for plane in range(STEPS * LAYERS):
            blocks = set(np.nonzero(written[plane])[0]) - {0}
            assert blocks == set(grant[:2]), plane
    engine.run()
    assert engine.allocator.free_blocks == engine.allocator.capacity
    engine.close()


# -- the programs' shape --------------------------------------------------------

@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_the_layers_are_traced_once_under_one_loop_over_the_steps(program):
    """``layers`` attention kernels in the program, every one inside the
    loop over the steps — not ``steps x layers`` unrolled (192 bodies a
    program at the published size, decode and every bucket)."""
    fn, args = _programs(tiny_model())[program]
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    kernels = _calls(jaxpr, "pallas_call")
    assert kernels == [True] * LAYERS
    # one loop of the model's (the kernels' own loops are inside them)
    loops = [e for e in jaxpr.eqns if e.primitive.name in LOOPS]
    assert len(loops) == 1
    assert jax.jit(fn).lower(*args).as_text().count("stablehlo.while") >= 1
    # the plane is a traced scalar: changing the steps changes no shape
    # but the gates' and the caches'
    more = tiny_model(total_ut_steps=STEPS + 2)
    assert len(_calls(jax.make_jaxpr(fn)(*args).jaxpr, "pallas_call")) == \
        len(_calls(jax.make_jaxpr(_programs(more)[program][0])(
            *_programs(more)[program][1]).jaxpr, "pallas_call"))


def test_the_loop_and_the_gate_are_named_scopes_of_the_lowered_program():
    fn, args = _programs(tiny_model())["decode"]
    text = jax.jit(fn).lower(*args).as_text(debug_info=True)
    assert "ut_loop" in text and "exit_gate" in text


def test_both_cache_buffers_are_donated_and_aliased_through_the_loop(
        served, tmp_path):
    """DSP601: the carried caches materialise as ``input_output_alias`` on
    the decode program and on a prefill bucket's — a loop that copied a
    carried cache would hold 8.15 GB twice at the published size."""
    model, params = served
    config = dict(ENGINE, telemetry={"enabled": True,
                                     "run_dir": str(tmp_path)})
    engine = InferenceEngine(model, params, config=config)
    rng = np.random.default_rng(3)
    for n in (5, 20):
        engine.submit(rng.integers(0, 256, size=n), max_new_tokens=4)
    engine.run()
    report = engine.verify_programs()
    assert report["programs_checked"] >= 3     # decode + two buckets
    assert report["errors"] == 0, report["diagnostics"]
    assert report["violations"] == 0, report["diagnostics"]
    compiled = engine.memory_ledger.compiled_programs()
    for name in ("serve_decode", "serve_prefill_16", "serve_prefill_32"):
        header = compiled[name].as_text().split("\n", 1)[0]
        assert header.count("alias") >= 3, name     # the key and two entries
    engine.close()


# -- the exit gate ---------------------------------------------------------------

def test_exit_masses_worked_by_hand():
    gates = jnp.asarray([[0.5, 0.0], [0.5, 1.0], [0.9, 0.3]])
    masses = np.asarray(exit_masses(gates))
    # half leaves at once, half of the rest next, the rest on the last
    # step whatever its gate says; a gate of 1 takes everything left
    np.testing.assert_allclose(masses[:, 0], [0.5, 0.25, 0.25])
    np.testing.assert_allclose(masses[:, 1], [0.0, 1.0, 0.0])
    np.testing.assert_allclose(masses.sum(axis=0), 1.0)
    one = np.asarray(exit_masses(jnp.asarray([[0.2]])))
    np.testing.assert_allclose(one, [[1.0]])


def test_a_threshold_that_would_let_tokens_leave_early_is_refused():
    with pytest.raises(AssertionError, match="differs by slot"):
        OuroConfig(early_exit_threshold=0.9)
    with pytest.raises(AssertionError, match="KV head"):
        OuroConfig(num_key_value_heads=4)


def test_decode_reports_the_loop_and_the_exit_distribution(served):
    model, params = served
    engine = InferenceEngine(model, params, config=ENGINE)
    rng = np.random.default_rng(5)
    for n in (7, 18):
        engine.submit(rng.integers(0, 256, size=n), max_new_tokens=8)
    for _ in range(6):
        engine.step()
    counters = {k: float(v) for k, v in engine.model_counters.items()}
    assert counters["ut_steps"] == STEPS
    assert counters["cache_planes"] == STEPS * LAYERS
    masses = [counters[f"exit_mass_step_{r + 1}"] for r in range(STEPS)]
    assert all(0.0 <= m <= 1.0 for m in masses)
    assert sum(masses) == pytest.approx(1.0, abs=1e-5)
    assert counters["exit_step_mean"] == pytest.approx(
        sum((r + 1) * m for r, m in enumerate(masses)), abs=1e-5)
    assert 1.0 <= counters["exit_step_mean"] <= STEPS
    engine.run()
    engine.close()


def test_the_served_tree_is_the_callers():
    model = tiny_model()
    params = seeded(model)
    assert model.serving().prepare_params(params) is params
