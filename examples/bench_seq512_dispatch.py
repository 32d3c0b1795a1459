"""seq-512 attention dispatch audit: XLA vs Pallas inside the full BERT step.

Seq 512 sits exactly on the dispatch boundary in
``ops/transformer/attention.py`` (XLA batched attention below 512, the
Pallas flash kernel at 512+).  This A/Bs the two impls inside the
END-TO-END BERT-large seq-512 pretraining step — the bench secondary —
rather than at the isolated-op level, because the winner can differ once
XLA schedules attention against the rest of the layer.

Each cell runs in a fresh subprocess (DS_FLASH_ATTENTION binds at trace
time; co-resident engines distort HBM).

Usage: python examples/bench_seq512_dispatch.py [batch ...]
"""

import os
import subprocess
import sys

_TRIAL = r"""
import os, time, math, numpy as np, jax
import deepspeed_tpu as deepspeed
from deepspeed_tpu.models import BertConfig, BertForPreTrainingTPU
from deepspeed_tpu.parallel import make_mesh

b = int(os.environ["T_B"]); steps = int(os.environ["T_S"])
dropout_p = 0.1
VOCAB = 30528
mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
cfg = BertConfig.bert_large(max_position_embeddings=512, vocab_size=VOCAB,
                            hidden_dropout_prob=dropout_p,
                            attention_probs_dropout_prob=dropout_p,
                            max_predictions_per_seq=80)
model = BertForPreTrainingTPU(cfg, compute_dtype=None)
engine, *_ = deepspeed.initialize(
    model=model, mesh=mesh,
    config={"train_batch_size": b, "steps_per_print": 10 ** 9,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
            "bf16": {"enabled": True}})
rng = np.random.default_rng(0)
ids = rng.integers(0, VOCAB, size=(b, 512)).astype(np.int32)
from bench import exact_count_mlm_labels
batch = {"input_ids": ids,
         "attention_mask": np.ones((b, 512), np.int32),
         "token_type_ids": np.zeros((b, 512), np.int32),
         "masked_lm_labels": exact_count_mlm_labels(rng, ids, 80),
         "next_sentence_labels": rng.integers(0, 2, size=(b,)).astype(np.int32)}
for _ in range(4):
    loss = engine.train_batch(iter([batch]))
float(jax.device_get(loss))
t0 = time.perf_counter()
for _ in range(steps):
    loss = engine.train_batch(iter([batch]))
v = float(jax.device_get(loss))
dt = time.perf_counter() - t0
assert math.isfinite(v)
print(f"AB_RESULT {b * steps / dt:.2f}")
"""


def run_cell(mode, batch, steps=12):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # APPEND to PYTHONPATH: clobbering it would drop whatever site
    # directories the environment put there
    pp = os.environ.get("PYTHONPATH", "")
    env = dict(os.environ, DS_FLASH_ATTENTION=mode, T_B=str(batch),
               T_S=str(steps),
               PYTHONPATH=f"{repo}:{pp}" if pp else repo)
    proc = subprocess.run([sys.executable, "-u", "-c", _TRIAL], env=env,
                          capture_output=True, text=True, timeout=1800,
                          cwd=repo)
    for line in proc.stdout.splitlines():
        if line.startswith("AB_RESULT "):
            return float(line.split()[1])
    tail = (proc.stdout + proc.stderr)[-300:].replace("\n", " ")
    oom = "RESOURCE_EXHAUSTED" in tail or "Out of memory" in tail
    return "OOM" if oom else f"fail: {tail[-120:]}"


def main():
    batches = [int(a) for a in sys.argv[1:]] or [16, 32]
    print("BERT-large seq512, dropout 0.1, Adam — samples/s by attention impl")
    for b in batches:
        for mode in ("always", "never"):
            label = {"always": "pallas", "never": "xla   "}[mode]
            r = run_cell(mode, b)
            print(f"  batch {b:3d}  {label}: {r}", flush=True)


if __name__ == "__main__":
    main()
