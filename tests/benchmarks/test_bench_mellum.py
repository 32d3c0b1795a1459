"""The cell ``mellum2_ep4.code8k``: its files load and say what the
configuration is, its counts are the numbers worked by hand below, a tiny
run of it is ``correct``, and planted faults in the program's mathematics
each read over the limit."""

import json
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import check, common, train
from benchmarks.models import mellum as bench_model
from benchmarks.reference import mellum as reference
from benchmarks.reference import ops
from deepspeed_tpu.models import mellum

from . import _tiny_mellum

MODEL = _tiny_mellum.MODEL
# the float32 program against the float32 reference at the tiny size: sound
# reads 7e-8 (loss) and 4e-7 (the worst leaf's gradient norm) — the order of
# the sums alone; the planted faults' worst leaf reads 0.08 (the auxiliary
# loss dropped: the routers' gradient) to 1.95 (the next head's K/V)
LIMIT = 1e-3
TRAIN_LIMITS = {"loss_gap": LIMIT, "grad_norm_gap": LIMIT,
                "grad_norm_median_gap": LIMIT, "delta_norm_gap": 5e-3,
                "eval_logit_gap": LIMIT}


@pytest.fixture(scope="module")
def cell():
    return common.load_cell("mellum2_ep4.code8k")


def test_the_cells_files_load_and_state_the_cut(cell):
    cfg, traffic = cell["config"], cell["traffic"]
    mc = cfg["model_config"]
    assert cell["chips"] == 1 and cfg["kind"] == "train"
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types",
                              "mlp_layer_types", "num_experts", "vocab_size"]
    # every published width, at the top level and as the model gets it
    for key, value in {"hidden_size": 2304, "head_dim": 128,
                       "num_attention_heads": 32, "num_key_value_heads": 4,
                       "moe_intermediate_size": 896, "sliding_window": 1024,
                       "num_experts_per_tok": 8}.items():
        assert cfg[key] == value == mc[key], key
    assert cfg["intermediate_size"] == 7168
    assert cfg["rope_parameters"] == mc["rope_parameters"] \
        == mellum.ROPE_PARAMETERS
    # the share: 16 of 64 experts, a quarter of the vocabulary, one period
    assert (mc["num_experts"], mc["published_num_experts"]) == (16, 64)
    assert (mc["vocab_size"], mc["source_vocab_size"]) == (24576, 98304)
    assert mc["layer_types"] == cfg["layer_types"] == \
        ["sliding_attention"] * 3 + ["full_attention"]
    assert "4 chips share each layer" in cfg["deployment"]
    assert set(cfg["assumed"]) >= {"qk_norm", "rotary_layout",
                                   "router_aux_loss", "mtp_head", "dropout",
                                   "optimizer", "remat", "loss_chunk"}
    assert cfg["mesh"] == {"data": 1}
    assert cfg["engine"]["zero_optimization"]["stage"] == 0
    assert traffic["seq_len"] == 8192 and traffic["generator"] == "lm_batches"
    assert set(traffic["limits"]["mellum2_ep4"]) == {
        "loss_gap", "grad_norm_gap", "grad_norm_median_gap",
        "delta_norm_gap", "eval_logit_gap"}
    assert {"train_full_attn_ms", "train_window_attn_ms",
            "train_moe_experts_ms", "train_full_attn_roofline",
            "train_mfu", "train_step_device_ms"} <= set(cell["per_layer"])
    assert cell["end_to_end"] == ["train_tokens_per_s", "setup_s"]


def test_parameter_count_by_hand(cell):
    mc = cell["config"]["model_config"]
    sizes = [int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        bench_model.param_shapes(mc), is_leaf=lambda x: isinstance(x, tuple))]
    attention = 2304 * (4096 + 2 * 512) + 4096 * 2304      # 21,233,664
    layer = attention + 2304 * 64 + 16 * 3 * 2304 * 896    # 120,471,552
    matrices = 4 * layer + 2 * 24576 * 2304                # 595,132,416
    norms = 4 * (2 * 2304 + 2 * 128) + 2304                # 21,760
    assert (attention, layer, matrices, norms) == (
        21233664, 120471552, 595132416, 21760)
    assert sum(sizes) == matrices + norms == 595154176
    # the program's own table is the same tree
    program = bench_model.build_program_model(mc, None).param_shapes()
    assert program == bench_model.param_shapes(mc)


def test_flops_and_kernel_counts_by_hand(cell):
    mc, traffic = cell["config"]["model_config"], cell["traffic"]
    s, b = 8192, 4
    # (query, key) pairs a sequence: causal 8192 * 8193 / 2; a window of
    # 1024: the first 1024 queries see 1..1024 keys, the other 7168 see 1024
    assert bench_model.keys_seen(s) == 33558528
    assert bench_model.keys_seen(s, 1024) == 524800 + 7168 * 1024 == 7864832
    assert bench_model.keys_seen(512, 1024) == 512 * 513 // 2
    assert bench_model.held_pairs_per_token(mc) == 2.0
    # forward, a token a layer: projections 2 * 21,233,664, the router
    # 2 * 147,456, two held experts 2 * 2 * 6,193,152
    per_token_layer = 2 * (21233664 + 147456 + 2 * 6193152)
    assert per_token_layer == 67534848
    attention = b * (33558528 + 3 * 7864832) * 4 * 128 * 32
    head = b * s * 2 * 2304 * 24576
    want = 3 * (b * s * 4 * per_token_layer + attention + head)
    assert bench_model.train_flops_per_step(mc, traffic, b) == want
    assert want / (b * s) == pytest.approx(1.493e9, rel=1e-3)
    counts = bench_model.counts(mc, traffic, b)
    # 7 products of 2 * 128 a scored pair and query head
    assert counts["gqa_attn_kernel_flops_per_layer"] == \
        7 * 2 * 128 * 32 * b * 33558528
    assert counts["window_attn_kernel_flops_per_layer"] == \
        7 * 2 * 128 * 32 * b * 7864832
    # 6 touches of each of 32 query-head and 4 KV-head [b, s, 128] tensors
    assert counts["gqa_attn_kernel_bytes_per_layer"] == \
        counts["window_attn_kernel_bytes_per_layer"] == \
        b * s * 128 * 2 * 6 * 36
    # 65,536 held pairs, 3 x (forward 2 * 3 * 2304 * 896 a pair)
    assert counts["moe_grouped_flops_per_layer"] == \
        3 * 2 * 65536 * 3 * 2304 * 896
    assert counts["moe_grouped_bytes_per_layer"] == \
        3 * 16 * 6193152 * 2 + 3 * 65536 * 2 * (2304 + 1792 + 896 + 2304)
    # the window layer does 0.234 of the full layer's attention work
    assert counts["window_attn_kernel_flops_per_layer"] / counts[
        "gqa_attn_kernel_flops_per_layer"] == pytest.approx(0.2344, abs=1e-4)
    # the harness's own count for the one full layer is the same kernel's
    assert bench_model.attention_shape(mc, traffic, b) == (
        1, b, 32, s, 128, True)
    from benchmarks import counts as harness_counts

    assert harness_counts.attention_kernel_flops_per_layer(
        b, 32, s, 128, True) == pytest.approx(
        counts["gqa_attn_kernel_flops_per_layer"], rel=2e-4)


# One traced step of the full layer, the window layers and the expert layers
# as the compiled step names them on a v5e (``lowered.compile().as_text()``
# for a described chip): each kernel under its own instruction name, and the
# instructions that CONSUME a kernel's result, whose text names it as an
# operand.  Seconds beside each.
_KERNELS = [
    ("%gqa_train_attention_fwd.1 = (bf16[4,8192,4096]{2,1,0}, f32[128,1,8192]"
     "{2,1,0}) custom-call(%copy.1350, %copy.1351, %copy.1393), "
     'custom_call_target="tpu_custom_call"', 0.020),
    ("%gqa_train_attention_bwd_dq.1 = bf16[4,8192,4096]{2,1,0} custom-call("
     '%copy.1354, %copy.1355), custom_call_target="tpu_custom_call"', 0.025),
    ("%gqa_train_attention_bwd_dkv.1 = (bf16[4,8192,512]{2,1,0}, bf16[4,8192,"
     '512]{2,1,0}) custom-call(%copy.1354), custom_call_target='
     '"tpu_custom_call"', 0.030),
    ("%window_train_attention_fwd.3 = (bf16[4,8192,4096]{2,1,0}, f32[128,1,"
     '8192]{2,1,0}) custom-call(%copy.1), custom_call_target='
     '"tpu_custom_call"', 0.006),
    ("%window_train_attention_bwd_dq.3 = bf16[4,8192,4096]{2,1,0} custom-call("
     '%copy.2), custom_call_target="tpu_custom_call"', 0.007),
    ("%window_train_attention_bwd_dkv.3 = (bf16[4,8192,512]{2,1,0}, bf16[4,"
     '8192,512]{2,1,0}) custom-call(%copy.2), custom_call_target='
     '"tpu_custom_call"', 0.008),
    ("%moe_grouped_matmul.48 = bf16[131072,1792]{1,0} custom-call(%fusion.7, "
     '%copy-done.3), custom_call_target="tpu_custom_call"', 0.004),
    ("%jvp_jit_moe_grouped_matmul__.2 = bf16[131072,1792]{1,0} custom-call("
     '%fusion.8), custom_call_target="tpu_custom_call"', 0.004),
    ("%moe_grouped_matmul_bwd_lhs.1 = bf16[131072,2304]{1,0} custom-call("
     '%fusion.9), custom_call_target="tpu_custom_call"', 0.005),
    ("%moe_grouped_matmul_bwd_rhs.14 = bf16[16,896,2304]{2,1,0} custom-call("
     '%fusion.9), custom_call_target="tpu_custom_call"', 0.006),
]
_CONSUMERS = [
    ("%copy.1382 = f32[4,8192,4096]{1,2,0} copy(%gqa_train_attention_bwd_dq.1)",
     0.009),
    ("%copy.1384 = f32[4,8192,4096]{1,2,0} copy("
     "%window_train_attention_bwd_dq.3)", 0.009),
    ("%broadcast_select_fusion.73 = bf16[131072,1792]{1,0} fusion("
     "%moe_grouped_matmul.48, %copy-done.273), kind=kLoop", 0.003),
    ("%add_any.26 = bf16[16,896,2304]{2,1,0} add(%copy-done.113, "
     "%moe_grouped_matmul_bwd_rhs.14)", 0.001),
    ("%fusion.981 = (bf16[131072,896]{1,0}, bf16[131072,896]{1,0}) fusion("
     "%broadcast_select_fusion.73, %moe_grouped_matmul_bwd_lhs.1), kind=kLoop",
     0.003),
]


def test_the_kernel_metrics_read_the_kernels_and_not_their_consumers(cell):
    """The four trace metrics of this cell match an instruction by its OWN
    name: an instruction that takes a kernel's result as an operand carries
    the kernel's name in its text too, and counted as a call it lifts the
    roofline share by calls found / ``calls_per_layer`` (a first form of
    these files read 65.6% where the kernels ran at 49.2%)."""
    from benchmarks import metrics, peaks
    from benchmarks.trace import xplane

    start, events = 0.0, []
    for text, seconds in _KERNELS + _CONSUMERS:
        events.append(xplane.Event(xplane.op_name(text), text, start,
                                   seconds))
        start += seconds
    flops, moved = 7.7e12, 1.1e9
    run = {"trace": xplane.Trace(ops={0: events}),
           "ctx": {"steps": 1, "window_s": start,
                   "device_kind": "TPU v5 lite",
                   "counts": {"attn_kernel_flops_per_layer": flops,
                              "attn_kernel_bytes_per_layer": moved}}}
    specs = metrics.load_all()
    got = {name: metrics.read_one(specs[name], run) for name in (
        "train_full_attn_ms", "train_window_attn_ms", "train_moe_experts_ms",
        "train_full_attn_roofline")}
    assert got["train_full_attn_ms"] == pytest.approx(75.0)
    assert got["train_window_attn_ms"] == pytest.approx(21.0)
    assert got["train_moe_experts_ms"] == pytest.approx(19.0)
    assert specs["train_full_attn_roofline"]["reader"]["args"][
        "calls_per_layer"] == 3
    assert got["train_full_attn_roofline"] == pytest.approx(
        peaks.roofline_percent(flops, moved, 0.075, "TPU v5 lite")[0])


def test_a_tiny_run_of_the_cell_is_correct(capsys):
    spec = _tiny_mellum.train_spec(TRAIN_LIMITS)
    ok = train.run_cell(spec, 2 ** 31 + 5, 0.3, 0, time.perf_counter(),
                        jax.devices()[:1])
    out = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
    line = json.loads(out[-1])
    assert ok is True and line["correct"] is True, out
    assert line["metrics"]["train_tokens_per_s"]["value"] > 0
    assert all(math.isfinite(x) for x in line["window_losses"])


# -- planted faults -----------------------------------------------------------

def _numbers(model, params, batch):
    """(loss, every leaf's gradient norm) of ``model`` on ``batch``."""
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: model.apply(p, batch, train=True)))(params)
    return float(loss), np.asarray([float(jnp.linalg.norm(g)) for g in
                                    jax.tree_util.tree_leaves(grads)])


@pytest.fixture(scope="module")
def sound():
    params = bench_model.init_params(MODEL, 5)
    ids = np.random.default_rng(5).integers(0, MODEL["vocab_size"],
                                            size=(2, 128), dtype=np.int32)
    batch = {"input_ids": jnp.asarray(ids)}
    totals = reference.batch_totals({"input_ids": ids})
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: reference.block_loss(p, batch, MODEL, None, None, None,
                                       ops.matmul, totals)))(params)
    norms = np.asarray([float(jnp.linalg.norm(g)) for g in
                        jax.tree_util.tree_leaves(grads)])
    return params, batch, float(loss), norms


def _gaps(model, sound):
    params, batch, ref_loss, ref_norms = sound
    loss, norms = _numbers(model, params, batch)
    worst, median = check.worst_and_median_gap(norms, ref_norms)
    return abs(loss - ref_loss) / ref_loss, worst, median


def _window_left_out(monkeypatch):
    return dict(MODEL, sliding_window=10 ** 6)


def _next_heads_kv_read(monkeypatch):
    kernel = mellum.flash_attention
    monkeypatch.setattr(mellum, "flash_attention", lambda q, k, v, *a: kernel(
        q, jnp.roll(k, 1, axis=2), jnp.roll(v, 1, axis=2), *a))
    return MODEL


def _aux_loss_dropped(monkeypatch):
    return dict(MODEL, router_aux_loss_coef=0.0)


def _renormalised_over_the_held_alone(monkeypatch):
    choose = mellum.expert_shard.choose_experts

    def held_only(*args, **kw):
        weights, ids = choose(*args, **kw)
        held = (ids >= MODEL["first_expert"]) & (
            ids < MODEL["first_expert"] + MODEL["num_experts"])
        kept = jnp.where(held, weights, 0.0)
        return kept / jnp.maximum(kept.sum(-1, keepdims=True), 1e-9), ids

    monkeypatch.setattr(mellum.expert_shard, "choose_experts", held_only)
    return MODEL


def _yarn_factor_on_sliding_layers(monkeypatch):
    inv_freq = mellum.rotary_inv_freq
    monkeypatch.setattr(mellum, "rotary_inv_freq", lambda config, kind: (
        inv_freq(config, kind)[0], 1.2772588722239782))
    return MODEL


FAULTS = {f.__name__.strip("_"): f for f in (
    _window_left_out, _next_heads_kv_read, _aux_loss_dropped,
    _renormalised_over_the_held_alone, _yarn_factor_on_sliding_layers)}


def test_the_sound_program_reads_under_the_limit(sound):
    gaps = _gaps(bench_model.build_program_model(MODEL, None), sound)
    assert max(gaps) < LIMIT, gaps


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_reads_over_the_limit(fault, sound, monkeypatch):
    cfg = FAULTS[fault](monkeypatch)
    # each fault's trace is its own: the layer's jit caches are keyed by
    # shapes, not by what the test patched
    jax.clear_caches()
    gaps = _gaps(bench_model.build_program_model(cfg, None), sound)
    jax.clear_caches()
    assert max(gaps) > 3 * LIMIT, (fault, gaps)
