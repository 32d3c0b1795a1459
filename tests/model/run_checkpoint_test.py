"""Model-scale checkpoint-resume continuity gate (standalone driver).

The reference trains real runs, saves mid-run, resumes in a fresh
process, and asserts the resumed loss curve matches the uninterrupted
one (``/root/reference/tests/model/Megatron_GPT2/run_checkpoint_test.py``
— its ``--checkpoint-num-layers``/LR-scheduler/csv-grep flow).  This
driver is that gate for the TPU framework, per config:

- ``baseline``   stage-0 Adam, dp=2, dropout on (pins rng-stream restore)
- ``zero1``      ZeRO-1, dp=2, dropout on
- ``zero2``      ZeRO-2, dp=2, dropout on
- ``zero2_offload`` ZeRO-2 + cpu_offload (eager host-parked state on CPU)
- ``pipeline``   PipelineModule over a pipe=2 x data=2 mesh
- ``elastic_dp`` ZeRO-2 saved at dp=4, RESUMED at dp=2 (elastic restore)

Flow per config (all three runs in FRESH subprocesses of this jax-free
driver; pytest's on-chip leg, whose process already holds the chip, runs
them in that process instead, each on a new engine):

1. uninterrupted run: ``steps`` steps, loss logged every step;
2. first half: ``steps//2`` steps, ``save_checkpoint``;
3. resume: fresh process, ``load_checkpoint``, remaining steps.

The resumed curve must match the uninterrupted run's second half
step-for-step (same-arithmetic resume; data is deterministic per
ABSOLUTE step, so a correct restore of master/optimizer/scale/rng/step
counters is exactly reproducible).  A dropped or double-counted ustep,
a stale optimizer moment, or a wrong LR-scheduler restore all shift the
curve and fail the gate.

Usage::

    python tests/model/run_checkpoint_test.py [--steps N] [--configs a,b]
"""

import argparse
import json
import os
import subprocess
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.abspath(os.path.join(HERE, "..", ".."))

VOCAB = 2048
SEQ = 32
BATCH = 8

CONFIGS = ("baseline", "zero1", "zero2", "zero2_async", "zero2_offload",
           "pipeline", "elastic_dp")
# legs that need >1 device (skipped on the single-chip TPU tier)
MULTI_DEVICE = {"baseline": 2, "zero1": 2, "zero2": 2, "zero2_async": 2,
                "zero2_offload": 1, "pipeline": 4, "elastic_dp": 4}


def _ds_config(name, dp):
    base = {"train_batch_size": BATCH, "steps_per_print": 10 ** 9,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "scheduler": {"type": "WarmupLR",
                          "params": {"warmup_min_lr": 0.0,
                                     "warmup_max_lr": 1e-3,
                                     "warmup_num_steps": 8}}}
    if name == "zero1":
        base["zero_optimization"] = {"stage": 1}
    elif name in ("zero2", "elastic_dp"):
        base["zero_optimization"] = {"stage": 2}
    elif name == "zero2_async":
        # the async checkpoint-subsystem leg: background commit +
        # retention; save-then-process-exit must still land a complete
        # checkpoint (non-daemon writer threads)
        base["zero_optimization"] = {"stage": 2}
        base["checkpoint"] = {"async_save": True, "keep_last_n": 2}
    elif name == "zero2_offload":
        base["zero_optimization"] = {"stage": 2, "cpu_offload": True}
    return base


def _dropout(name):
    # dropout ON where the leg pins the rng-stream restore (ustep); off
    # for legs where per-device generation order may differ across the
    # save/resume topology change
    return 0.1 if name in ("baseline", "zero1", "zero2",
                           "zero2_async") else 0.0


# ---------------------------------------------------------------- child
def _child(args):
    if os.environ.get("DS_CKPT_FORCE_CPU") == "1":
        import jax
        jax.config.update("jax_platforms", "cpu")
    import jax

    sys.path.insert(0, REPO)
    import deepspeed_tpu as deepspeed
    from deepspeed_tpu.parallel import make_mesh

    name = args.config
    dp = args.dp
    steps = args.steps

    if name == "pipeline":
        from deepspeed_tpu.runtime.pipe import LayerSpec, PipelineModule

        class Dense:
            def __init__(self, din, dout, act=True):
                self.din, self.dout, self.act = din, dout, act

            def init(self, rng):
                import jax.numpy as jnp  # noqa: F401
                k = jax.random.normal(rng, (self.din, self.dout)) * 0.05
                return {"w": k}

            def apply(self, params, x):
                import jax.numpy as jnp
                y = x @ params["w"]
                return jnp.tanh(y) if self.act else y

        def mse(pred, target):
            import jax.numpy as jnp
            return jnp.mean((pred - target) ** 2)

        H = 64
        specs = [LayerSpec(Dense, H, H) for _ in range(3)] + [
            LayerSpec(Dense, H, H, act=False)]
        module = PipelineModule(specs, loss_fn=mse)
        mesh = make_mesh({"pipe": 2, "data": dp // 2},
                         devices=jax.devices()[:dp])
        cfg = dict(_ds_config(name, dp),
                   train_micro_batch_size_per_gpu=BATCH // (dp // 2),
                   gradient_accumulation_steps=1)
        engine, *_ = deepspeed.initialize(model=module, config=cfg,
                                          mesh=mesh)

        def batch_for(step):
            # cycle 4 fixed batches (still deterministic per absolute
            # step): a fresh random regression batch per step keeps the
            # toy loss flat, which would trip the did-it-train check
            rng = np.random.default_rng(1000 + step % 4)
            x = rng.normal(size=(BATCH, H)).astype(np.float32)
            return (x, np.tanh(x) @ np.eye(H, dtype=np.float32))
    else:
        from deepspeed_tpu.models.bert import (BertConfig,
                                               BertForPreTrainingTPU)

        cfg_m = BertConfig(
            vocab_size=VOCAB, hidden_size=128, num_hidden_layers=2,
            num_attention_heads=4, intermediate_size=256,
            max_position_embeddings=128,
            hidden_dropout_prob=_dropout(name),
            attention_probs_dropout_prob=_dropout(name))
        model = BertForPreTrainingTPU(cfg_m)
        mesh = make_mesh({"data": dp}, devices=jax.devices()[:dp])
        engine, *_ = deepspeed.initialize(
            model=model, config=_ds_config(name, dp), mesh=mesh)

        def batch_for(step):
            rng = np.random.default_rng(1000 + step)
            ids = rng.integers(10, VOCAB, size=(BATCH, SEQ)).astype(np.int32)
            labels = np.full((BATCH, SEQ), -100, np.int32)
            for r in range(BATCH):
                pos = rng.permutation(SEQ)[:4]
                labels[r, pos] = ids[r, pos]
            return {"input_ids": ids, "masked_lm_labels": labels,
                    "next_sentence_label": rng.integers(
                        0, 2, size=(BATCH,)).astype(np.int32)}

    if args.load:
        path, _ = engine.load_checkpoint(args.load)
        assert path is not None, f"load_checkpoint({args.load}) found nothing"

    lines = []
    for _ in range(steps):
        step = engine.global_steps  # absolute step drives the data
        loss = engine.train_batch(iter([batch_for(step)]))
        val = float(np.asarray(jax.device_get(loss)))
        lines.append(f"step: {step} loss: {val:.6f}")

    if args.save:
        engine.save_checkpoint(args.save)

    with open(args.log, "w") as f:
        f.write("\n".join(lines) + "\n")
    print("CHILD_OK", flush=True)


# ----------------------------------------------------------- orchestrate
def _run_child(config, steps, dp, log, save=None, load=None, force_cpu=True,
               in_process=False):
    if in_process:
        # the caller's process holds the chip (pytest's ``-m tpu`` tier): a
        # child could not open it, so the phase runs here on a new engine
        return _child(argparse.Namespace(config=config, steps=steps, dp=dp,
                                         log=log, save=save, load=load))
    env = dict(os.environ)
    if force_cpu:
        env["DS_CKPT_FORCE_CPU"] = "1"
        flags = env.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
    cmd = [sys.executable, "-u", os.path.abspath(__file__), "--phase", "child",
           "--config", config, "--steps", str(steps), "--dp", str(dp),
           "--log", log]
    if save:
        cmd += ["--save", save]
    if load:
        cmd += ["--load", load]
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=1200)
    if "CHILD_OK" not in proc.stdout:
        raise RuntimeError(
            f"child failed [{config} steps={steps} dp={dp}]:\n"
            f"{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}")


def _grep(path):
    out = {}
    with open(path) as f:
        for line in f:
            if line.startswith("step: "):
                _, s, _, v = line.split()
                out[int(s)] = float(v)
    return out


def run_config(name, steps, out_dir, force_cpu=True, rtol=1e-4,
               in_process=False):
    dp = MULTI_DEVICE[name]
    resume_dp = 2 if name == "elastic_dp" else dp
    half = steps // 2
    full_log = os.path.join(out_dir, f"{name}_full.log")
    first_log = os.path.join(out_dir, f"{name}_first.log")
    resume_log = os.path.join(out_dir, f"{name}_resume.log")
    ckpt = os.path.join(out_dir, f"{name}_ckpt")

    _run_child(name, steps, dp, full_log, force_cpu=force_cpu,
               in_process=in_process)
    _run_child(name, half, dp, first_log, save=ckpt, force_cpu=force_cpu,
               in_process=in_process)
    _run_child(name, steps - half, resume_dp, resume_log, load=ckpt,
               force_cpu=force_cpu, in_process=in_process)

    full = _grep(full_log)
    first = _grep(first_log)
    resume = _grep(resume_log)
    # sanity: the first-half run reproduces the full run's first half
    for s in first:
        np.testing.assert_allclose(first[s], full[s], rtol=rtol, err_msg=(
            f"[{name}] pre-save divergence at step {s} (harness bug)"))
    assert sorted(resume) == sorted(s for s in full if s >= half), (
        f"[{name}] resumed step numbering wrong: {sorted(resume)}")
    for s in resume:
        np.testing.assert_allclose(resume[s], full[s], rtol=rtol, err_msg=(
            f"[{name}] resumed curve diverged at step {s}: "
            f"{resume[s]} vs uninterrupted {full[s]}"))
    # the run must actually train across the boundary
    fl = [full[s] for s in sorted(full)]
    assert fl[-1] < fl[0], f"[{name}] did not train: {fl}"
    return {"steps": steps, "half": half,
            "final_resumed": resume[max(resume)],
            "final_full": full[max(full)]}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--phase", default="orchestrate")
    ap.add_argument("--config", default=None)
    ap.add_argument("--configs", default=",".join(CONFIGS))
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--log", default=None)
    ap.add_argument("--save", default=None)
    ap.add_argument("--load", default=None)
    ap.add_argument("--out", default="/tmp/ds_ckpt_test")
    ap.add_argument("--tpu", action="store_true",
                    help="run on the real chip: single-device legs only, "
                    "no CPU forcing")
    args = ap.parse_args()

    if args.phase == "child":
        return _child(args)

    os.makedirs(args.out, exist_ok=True)
    names = [c for c in args.configs.split(",") if c]
    results = {}
    for name in names:
        if args.tpu and MULTI_DEVICE[name] > 1:
            print(f"[{name}] SKIP (needs {MULTI_DEVICE[name]} devices)",
                  flush=True)
            continue
        results[name] = run_config(name, args.steps, args.out,
                                   force_cpu=not args.tpu)
        print(f"[{name}] continuity OK "
              f"(resumed final {results[name]['final_resumed']:.6f} == "
              f"uninterrupted {results[name]['final_full']:.6f})", flush=True)
    print(json.dumps({"run_checkpoint_test": "ALL PASS",
                      "configs": list(results)}))


if __name__ == "__main__":
    main()
