"""``chip_smoke.py`` at a tiny size on the CPU.

The script is the driver's check that the system starts on the chip; it
runs there at the real sizes.  Here its phase functions walk the same code
— the same entry points, the same checks — through their size arguments,
so that a wrong path, argument or check is found without chip time (the
first rehearsal of the ``on-chip-measurement`` guide), and the four-chip
phase runs on four of conftest's virtual CPU devices (the second).  The
offload phase has no CPU form: in-jit host placement does not exist on
this backend.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

import chip_smoke
from deepspeed_tpu.models import BertConfig, GPT2Config
from deepspeed_tpu.parallel import make_mesh
from deepspeed_tpu.runtime.compilation import CompileStats

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture
def harness():
    stats = CompileStats()
    yield stats, chip_smoke._GeometryLog()
    stats.close()


def tiny_gpt2(**kw):
    return GPT2Config(vocab_size=256, hidden_size=64, num_layers=2,
                      num_heads=4, max_position_embeddings=64,
                      embd_dropout=0.0, attn_dropout=0.0, resid_dropout=0.0,
                      **kw)


def test_train_bert_phase(harness, capsys):
    cfg = BertConfig(vocab_size=128, hidden_size=32, num_hidden_layers=2,
                     num_attention_heads=2, max_position_embeddings=32,
                     hidden_dropout_prob=0.0,
                     attention_probs_dropout_prob=0.0,
                     max_predictions_per_seq=4)
    record = chip_smoke.train_bert(
        make_mesh({"data": 1}), 0, *harness, cfg=cfg, batch=4, seq=32,
        n_pred=4, steps=5, expect_kernel=False)
    assert record["steps"] == 5 and len(record["losses"]) == 5
    assert record["compiled_after_first_step"] == 0
    assert record["losses"][4] < record["losses"][0]
    assert set(record["fence_check"]) == {"block_until_ready_seconds",
                                          "device_get_seconds"}
    # the phase printed exactly the record it returned, as one JSON line
    assert json.loads(capsys.readouterr().out.strip()) == record


def test_train_gpt2_phase(harness):
    record = chip_smoke.train_gpt2(
        make_mesh({"data": 1}), 0, *harness, cfg=tiny_gpt2(), batch=4,
        seq=64, steps=3, expect_kernel=False)
    assert record["phase"] == "train.gpt2_medium"
    assert record["losses"][2] < record["losses"][0]
    assert record["pallas_kernel_in_step"] is False
    # the CPU mesh has no Pallas kernel in its step: asking for one fails
    # the phase, which is how a silent hand-off to XLA attention shows
    with pytest.raises(AssertionError, match="Pallas kernel"):
        chip_smoke.train_gpt2(
            make_mesh({"data": 1}), 0, *harness, cfg=tiny_gpt2(), batch=4,
            seq=64, steps=3, expect_kernel=True)


def test_serve_phase(harness):
    inference = {"kv_block_size": 8, "kv_blocks": 4 * 8 + 1,
                 "max_batch_slots": 4, "max_seq_len": 64,
                 "prefill_buckets": [16, 32], "token_budget": 256,
                 "max_new_tokens": 4, "weights_dtype": "bfloat16"}
    record = chip_smoke.serve(
        0, *harness, cfg=tiny_gpt2(), inference=inference,
        prompt_lens=(5, 30, 12, 9, 21, 17, 26, 7), new_tokens=4, n_check=2)
    assert record["requests"] == 8 and record["generated_tokens"] == 32
    assert record["compiled_after_warmup"] == 0
    assert [c["prompt_tokens"] for c in
            record["checked_against_reference"]] == [5, 7]
    assert all(secs > 0 for secs in
               record["compile_seconds_by_program"].values())


def test_multichip_phase_on_four_virtual_devices(harness):
    zero2, zero3 = chip_smoke.multichip(
        4, 0, *harness, cfg=tiny_gpt2(), batch=8, seq=64, steps=2)
    for record, stage in ((zero2, 2), (zero3, 3)):
        assert record["phase"] == f"chips4.zero{stage}"
        assert len(record["master_shard_devices"]) == 4
        assert len(set(record["master_shard_bytes"])) == 1
        assert all(record["collectives_in_step"].values())
        assert len(record["dp1_losses"]) == len(record["losses"]) == 2


@pytest.mark.parametrize("alone", [False, True])
def test_script_fails_and_prints_no_result_without_a_tpu(tmp_path, alone):
    """No accelerator: another exit code than 0 and no ``"ok": true`` —
    from the checkout, and from a directory that holds the script and
    nothing else of the repo."""
    script = os.path.join(REPO, "chip_smoke.py")
    if alone:
        script = shutil.copy(script, tmp_path / "chip_smoke.py")
    proc = subprocess.run(
        [sys.executable, str(script)], cwd=tmp_path, capture_output=True,
        text=True, timeout=120, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr
