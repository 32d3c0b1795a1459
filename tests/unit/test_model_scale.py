"""Model-scale convergence gates (CI tier).

The reference gates releases on model-level runs: Megatron-GPT2
functional tests compare DS-config loss curves against a baseline run
(``tests/model/Megatron_GPT2/run_func_test.py``), and BingBertSquad
asserts EM/F1 after a fine-tune (``test_e2e_squad.py``).  The full-size
analog lives in ``tests/model/run_func_test.py`` (standalone; minutes on
the real chip).  These tests run the same harness at CI scale:

- slow tier (CPU): real-WIDTH BERT-base (h768 L12 i3072 — the config is
  what's being gated; seq/steps shrink to fit one CPU core) with the loss
  curve pinned under ``tests/unit/baselines/model_scale.json``
  (regenerate with ``DS_UPDATE_BASELINES=1``), plus the QA EM/F1 gate.
- tpu tier (``DS_TEST_TPU=1 pytest -m tpu``): the full few-hundred-step
  BERT-base seq128 matrix + QA gate, on-chip.
"""

import os

import numpy as np
import pytest

from ..model import func_harness as H

BASELINES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "baselines", "model_scale.json")


@pytest.mark.slow
def test_bert_base_mlm_curve_pinned(cpu_devices):
    """Real-width BERT-base MLM loss curve on fixed data, pinned."""
    from deepspeed_tpu.models.bert import BertForPreTrainingTPU

    steps, batch, seq = 40, 8, 32
    data = H.mlm_batches(seed=17, n_batches=4, batch=batch, seq=seq)
    model = BertForPreTrainingTPU(H.bert_base_config(seq, dropout=0.0))
    engine = H.make_engine(
        model, {"train_batch_size": batch, "steps_per_print": 10 ** 9,
                "optimizer": {"type": "Adam", "params": {"lr": 3e-4}}})
    curve = H.train_curve(engine, data, steps, sample_every=8)
    assert curve[-1] < curve[0], f"no convergence: {curve}"
    pinned = H.load_or_update_baseline(BASELINES, "bert_base_mlm_seq32",
                                       curve)
    np.testing.assert_allclose(curve, pinned, rtol=2e-2,
                               err_msg="curve drifted from pinned baseline")


@pytest.mark.slow
def test_qa_gate_real_data():
    """Extractive-QA EM/F1 gate on the vendored REAL dataset (qa_mini,
    SQuAD v1.1 format — reference BingBertSquad/test_e2e_squad.py).
    Calibrated: healthy run EM ~0.94 / F1 ~0.95 vs gates 0.75/0.85."""
    from ..model import run_func_test as R

    R.run_qa_gate(steps=250, batch=32, seq=128, em_min=0.75, f1_min=0.85)


@pytest.mark.slow
def test_qa_gate_fails_under_broken_mask():
    """Falsifiability: the same gate must FAIL when the attention mask is
    deliberately broken (question hidden from the encoder at eval).  Each
    passage carries three questions with different answers and the
    question slot is fixed-width, so a model that cannot attend the
    question caps near EM 1/3 (measured: EM 0.15 / F1 0.27) — if this
    test ever fails, the gate has stopped measuring attention."""
    from ..model import run_func_test as R

    R.run_qa_gate(steps=250, batch=32, seq=128, em_min=0.75, f1_min=0.85,
                  corrupt_mask=True, _expect_fail=True)


@pytest.mark.slow
def test_checkpoint_resume_continuity_matrix():
    """Train -> save -> resume-in-a-fresh-process -> the resumed loss
    curve must match the uninterrupted run step-for-step (reference
    ``tests/model/Megatron_GPT2/run_checkpoint_test.py``).  The
    large-model checkpoint roundtrips all live in this slow tier; CPU
    tier runs the cheapest legs plus the async checkpoint-subsystem leg,
    and the full 7-config matrix (incl. pipeline and the elastic
    DP-degree change) is the standalone driver
    ``tests/model/run_checkpoint_test.py``."""
    import tempfile

    from ..model import run_checkpoint_test as R

    with tempfile.TemporaryDirectory() as tmp:
        for name in ("baseline", "zero2", "zero2_async", "elastic_dp"):
            R.run_config(name, steps=8, out_dir=tmp, force_cpu=True)


@pytest.mark.tpu
def test_checkpoint_resume_continuity_on_chip():
    """One continuity leg on the real chip (single-device configs only:
    the tier has one TPU).  In this process: it holds the chip, and a
    child that needs the chip then fails on libtpu's lock file.  Fresh
    processes on the chip are the standalone driver's job
    (``python tests/model/run_checkpoint_test.py --tpu``)."""
    import tempfile

    from ..model import run_checkpoint_test as R

    with tempfile.TemporaryDirectory() as tmp:
        R.run_config("zero2_offload", steps=8, out_dir=tmp, force_cpu=False,
                     in_process=True)


@pytest.mark.tpu
def test_bert_base_full_matrix_on_chip():
    """The full model-scale flow on the real chip: config-matrix loss
    parity at BERT-base seq128 + the QA EM/F1 gate (reference
    run_func_test.py + test_e2e_squad.py, end to end)."""
    import tempfile

    from ..model import run_func_test as R

    with tempfile.TemporaryDirectory() as tmp:
        curves = R.run_matrix(steps=120, batch=32, seq=128, out_dir=tmp)
    R.check_matrix(curves, rtol=0.05)
    R.run_qa_gate(steps=250, batch=32, seq=128, em_min=0.75, f1_min=0.85)
