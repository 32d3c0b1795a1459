"""``correct`` has to be able to come out false.

Two controls, at a size a test run can hold (the on-chip readings at the
cells' own sizes are in PERF.md section 2):

- the reference computed in float8 in the program's place fails limits that
  the program passes — here with dropout off and the program in float32,
  because at this tiny size (1k tokens a step instead of 16k, 64 wide) the
  masks' noise and bfloat16's own are as large as float8's;
- a run with the timed path broken underneath (``benchmarks/faults.py``:
  a training step that returns its state unchanged or leaves half of the
  batch out, another learning rate than the configuration states, a
  served token altered where it is produced) reads ``correct`` false,
  driving everything of a run but the look for a chip.
"""

import json
import time

import jax
import pytest

from benchmarks import check, faults, serve, train

from . import _tiny

# tiny-size limits, set the way the cells' are, from readings at this size
# over seeds 1-3: sound runs' largest (loss 3e-7, gradient 5e-7, change
# 1.4e-5, evaluated logits 2e-6) and the float8 control's smallest
# (gradient 0.0117, change 0.017, evaluated logits 0.03; its loss moves by
# 2e-4 at most, so the loss is there for other faults)
TRAIN_LIMITS = {"loss_gap": 1e-3, "grad_norm_gap": 3e-3,
                "grad_norm_median_gap": 1e-3, "head_grad_norm_gap": 1e-3,
                "delta_norm_gap": 5e-3,
                "head_delta_norm_gap": 1e-3, "eval_logit_gap": 1e-3}
# served tokens, float32 weights: sound runs read 0 (the served token is
# the reference's best); the control reads 0.017, 0.013, 0.011 at seeds 1, 4, 8 (and 0 to 0.008
# at others: a widest gap swings by its nature, more so over 512 tokens)
SERVE_LIMITS = {"served_logit_gap": 1e-3}


def _last_line(capsys):
    out = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
    return json.loads(out[-1]), out


@pytest.fixture(scope="module")
def devices():
    return jax.devices()[:1]


def test_sound_training_run_is_correct_and_fp8_control_is_not(devices):
    spec = _tiny.train_spec(TRAIN_LIMITS, dropout=0.0, bf16=False)
    ready = train.setup(spec, 3, devices)
    program, pool = ready["program"], ready["pool"]
    train.free(ready.pop("engine"))
    reference = train.reference_steps(spec, 3, pool, devices)
    control = train.reference_steps(spec, 3, pool, devices, "fp8")
    def logits(precision):
        return train.reference_eval_logits(
            spec, 3, pool[0], program["eval_rows"],
            program["eval_positions"], devices, precision)

    named = train.named_leaves(spec["config"])
    sound = check.Comparison(TRAIN_LIMITS)
    assert check.compare_training(sound, program, reference, *named)
    assert sound.add("eval_logit_gap", train.logit_rms_gap(
        program["eval_logits"], logits("float32")))
    low = check.Comparison(TRAIN_LIMITS)
    assert not check.compare_training(low, control, reference, *named)
    assert not low.add("eval_logit_gap", train.logit_rms_gap(
        logits("fp8"), logits("float32")))
    failed = {name for name, *_, ok in low.rows if not ok}
    assert failed >= {"grad_norm_gap", "delta_norm_gap", "eval_logit_gap"}


def _failed_checks(out):
    return {l.split()[1] for l in out
            if l.startswith("check ") and "FAILED" in l}


def test_training_step_that_returns_its_state_unchanged_is_caught(
        devices, capsys):
    spec = _tiny.train_spec(TRAIN_LIMITS, dropout=0.0, bf16=False)
    ok = train.run_cell(spec, 4, 0.5, 0, time.perf_counter(), devices,
                        wrap_engine=faults.FrozenStep)
    line, out = _last_line(capsys)
    assert ok is False and line["correct"] is False
    assert {"delta_norm_gap", "head_delta_norm_gap"} <= _failed_checks(out)


def test_training_step_that_leaves_half_the_batch_out_is_caught(
        devices, capsys):
    spec = _tiny.train_spec(TRAIN_LIMITS, dropout=0.0, bf16=False)
    ok = train.run_cell(spec, 4, 0.5, 0, time.perf_counter(), devices,
                        wrap_engine=faults.HalfBatch)
    line, out = _last_line(capsys)
    assert ok is False and line["correct"] is False
    assert {"grad_norm_median_gap",
            "head_grad_norm_gap"} <= _failed_checks(out)


def test_another_learning_rate_than_the_configurations_is_caught(
        devices, capsys):
    """lr x 1.5 in the program, the configuration's in the reference: the
    weights' change is half as large again in every leaf."""
    spec = _tiny.train_spec(TRAIN_LIMITS, dropout=0.0, bf16=False)
    params = dict(_tiny.ADAM, lr=1.5 * _tiny.ADAM["lr"])
    spec["config"]["engine"]["optimizer"] = {"type": "Adam",
                                             "params": params}
    ok = train.run_cell(spec, 4, 0.5, 0, time.perf_counter(), devices)
    line, out = _last_line(capsys)
    assert ok is False and line["correct"] is False
    assert "head_delta_norm_gap" in _failed_checks(out)
    assert "grad_norm_median_gap" not in _failed_checks(out)


def test_sound_training_run_prints_the_contracts_line(devices, capsys):
    spec = _tiny.train_spec(TRAIN_LIMITS, dropout=0.0, bf16=False)
    ok = train.run_cell(spec, 5, 0.5, 0, time.perf_counter(), devices)
    line, out = _last_line(capsys)
    assert ok is True and line["correct"] is True and line["failed"] == 0
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert line["attempted"] >= 3 and line["metrics"]["setup_s"]["value"] > 0
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    checks = [l.split()[1] for l in out if l.startswith("check ")]
    assert checks == ["loss_gap_step1", "loss_gap_step2", "loss_gap_step3",
                      "grad_norm_gap", "grad_norm_median_gap",
                      "head_grad_norm_gap", "delta_norm_gap",
                      "head_delta_norm_gap",
                      "eval_logit_gap", "window_losses_finite",
                      "no_compile_in_window"]


def test_served_tokens_sound_and_altered(devices, capsys):
    spec = _tiny.serve_spec(SERVE_LIMITS)
    ok = serve.run_cell(spec, 6, 1.0, 0, time.perf_counter(), devices)
    line, out = _last_line(capsys)
    assert ok is True and line["correct"] is True
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert line["attempted"] > 10
    ok = serve.run_cell(spec, 6, 1.0, 0, time.perf_counter(), devices,
                        wrap_engine=faults.AlteredToken)
    line, out = _last_line(capsys)
    assert ok is False and line["correct"] is False
    assert any(l.startswith("check served_logit_gap") and "FAILED" in l
               for l in out)


def test_open_loop_mix_runs_and_reports_latencies(devices, capsys):
    """The open-loop generator and its traffic file are data a later PR
    turns into a cell; they run today."""
    import os

    from benchmarks import common

    spec = _tiny.serve_spec(SERVE_LIMITS)
    traffic = common.load_traffic("chat_open")
    traffic["pairs"] = spec["traffic"]["pairs"]
    traffic.update(rate_per_s=150.0, horizon_s=5.0, limits={
        "tiny": SERVE_LIMITS})
    spec["traffic"] = traffic
    ok = serve.run_cell(spec, 9, 1.0, 0, time.perf_counter(), devices)
    line, out = _last_line(capsys)
    assert ok is True and line["correct"] is True
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s",
                                    "ttft_p95_ms", "tpot_p50_ms"}
    assert line["metrics"]["ttft_p95_ms"]["value"] > 0
    assert 50 < line["attempted"] < 400     # ~150 arrivals in the second


@pytest.mark.parametrize("seed", [1, 4, 8])
def test_fp8_control_moves_served_tokens_past_the_limit(devices, seed):
    spec = _tiny.serve_spec(SERVE_LIMITS)
    loop = serve.setup(spec, seed, devices)
    for _ in range(200):
        loop.step()
    sample = serve.sample_finished(loop.finished, seed, n=12)
    # the loop's own count of output tokens agrees with the program's
    # counter (which the window does not read) but for the warm-up's
    assert loop.tokens == loop.engine.generated_tokens - 2 * len(
        spec["config"]["engine"]["inference"]["prefill_buckets"])
    serve.free(loop)
    sound = serve.reference_gaps(spec, seed, sample)
    control = serve.reference_gaps(spec, seed, sample, "fp8")
    assert len(sound) > 50
    assert sound.max() <= SERVE_LIMITS["served_logit_gap"]
    assert control.max() > 3 * SERVE_LIMITS["served_logit_gap"]


def test_leaf_gaps_measure_against_the_median_leaf():
    reference = [1.0, 2.0, 1e-9, 4.0]        # one gradient all but zero
    gaps = check.worst_and_median_gap
    assert gaps(reference, reference) == (0.0, 0.0)
    # the tiny leaf is judged against the median leaf (1.5), not itself
    assert gaps([1.0, 2.0, 0.3, 4.0], reference)[0] == \
        pytest.approx(0.3 / 1.5, rel=1e-6)
    assert gaps([1.0, 2.5, 1e-9, 4.0], reference)[0] == pytest.approx(0.25)
    assert gaps([1.0, 2.0], reference) == (float("inf"), float("inf"))
    # every leaf half as large again (another learning rate): the median
    # leaf says so as loudly as the worst
    assert gaps([1.5, 3.0, 1.5e-9, 6.0], reference) == \
        pytest.approx((0.5, 0.5))


def test_only_the_leaves_the_configuration_names_are_left_out():
    paths = ["bert/pooler/kernel", "bert/pooler/bias",
             "bert/encoder/layer_0/qkv/bias", "cls/transform/kernel"]
    left = check.left_out(paths, ["bert/pooler/*", "*/qkv/bias"])
    assert left.tolist() == [True, True, True, False]
    assert not check.left_out(paths, []).any()
    reference, program = [1.0] * 4, [3.0, 1.0, 1.0, 1.2]
    assert check.worst_and_median_gap(program, reference)[0] == 2.0
    assert check.worst_and_median_gap(program, reference, left)[0] == \
        pytest.approx(0.2)
    # a wild leaf that is not named decides, however many leaves there are
    many, wild = [1.0] * 400, [1.0] * 399 + [1.6]
    assert check.worst_and_median_gap(wild, many)[0] == pytest.approx(0.6)


def test_the_cells_name_their_leaves_with_a_reason_each():
    from benchmarks import common

    for cell in ("bert_large.seq512", "bert_large.seq128"):
        spec = common.load_cell(cell)
        paths, named = train.named_leaves(spec["config"])
        assert set(named) == {"leaves_left_out", "head_leaves"}
        assert set(named["leaves_left_out"]) <= {"grad_norms", "delta_norms"}
        groups = [*named["leaves_left_out"].values(), named["head_leaves"]]
        for patterns in groups:
            assert all(len(reason) > 20 for reason in patterns.values())
            # every pattern finds leaves, and they stay a small part
            assert all(check.left_out(paths, [p]).any() for p in patterns)
            assert 0 < check.left_out(paths, patterns).sum() <= 0.1 * len(
                paths)
        # every number the comparison adds has a limit in the cell's file
        limits = spec["traffic"]["limits"][spec["config"]["name"]]
        assert set(limits) == {
            "loss_gap", "grad_norm_gap", "grad_norm_median_gap",
            "head_grad_norm_gap", "delta_norm_gap", "head_delta_norm_gap",
            "eval_logit_gap"}
        # a frozen step reads 1.0 in both delta numbers, another learning
        # rate (x 1.5) 0.5: over each limit
        assert 0.5 > limits["delta_norm_gap"] > limits["head_delta_norm_gap"]
