"""Replicated-fleet child script for the integrity chaos e2e tests.

Driven by ``deepspeed_tpu.launcher.launch`` with the elastic supervisor
armed.  Every process is one fleet rank holding a FULL replica: a dp=1
mesh on one virtual CPU device, consuming the complete global batch
stream — so all ranks' (master, optimizer) states are bit-identical
step for step without cross-process collectives, which is exactly the
pure-dp invariant the fingerprint consensus votes on.  The integrity
plane is armed (telemetry run dir = the launcher's shared
``DS_TELEMETRY_DIR``); rank 0 commits a synchronous checkpoint per
step; every life ``auto_resume``s.

Chaos (first life only, seeded, one target rank):

- ``DS_CHAOS_BITFLIP_STEP`` — the target rank's master state takes a
  single seeded bitflip right before that optimizer step: silent SDC.
  The consensus names the rank, every healthy rank exits 87, the
  supervisor evicts the slot and resizes; respawned lives roll back to
  the last committed checkpoint and re-train to completion.
- ``DS_CHAOS_HANG_STEP`` — the target rank wedges in the batch fetch
  before entering that step (never beats it).  The healthy majority's
  hang quorum convicts it after ``DS_INTEGRITY_PEER_TIMEOUT`` seconds
  and exits 87 — ONE eviction resize instead of N local watchdog
  timeouts (the local watchdog is armed far looser to prove which
  mechanism recovered).

argv: <ckpt_dir> <out_dir>   (telemetry dir rides DS_TELEMETRY_DIR)
"""

import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import deepspeed_tpu as deepspeed  # noqa: E402
from deepspeed_tpu.parallel import make_mesh  # noqa: E402
from deepspeed_tpu.resilience.chaos import ChaosMonkey  # noqa: E402
from deepspeed_tpu.resilience.constants import (  # noqa: E402
    FleetIntegrityError, TrainingDivergedError)
from deepspeed_tpu.runtime.dataloader import RepeatingLoader  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from simple_model import SimpleModel, random_dataset  # noqa: E402

HIDDEN = 16
GLOBAL_BATCH = 16
TOTAL_STEPS = 10
DATASET_SAMPLES = 80


def _env_int(name, default=0):
    return int(os.environ.get(name, "") or default)


def _env_float(name, default=0.0):
    return float(os.environ.get(name, "") or default)


def main():
    ckpt_dir, out_dir = sys.argv[1], sys.argv[2]
    rank = _env_int("DS_PROCESS_ID", 0)
    # full-replica fleet: every rank computes the complete global batch
    # independently (bit-identical states without cross-process
    # collectives), so the jax multi-controller rendezvous must NOT
    # engage — the DS_PROCESS_ID/DS_NUM_PROCESSES fleet identity still
    # reaches the integrity plane
    os.environ.pop("DS_COORDINATOR", None)
    mesh = make_mesh({"data": 1}, devices=jax.devices("cpu")[:1])

    config = {
        "train_batch_size": GLOBAL_BATCH,
        "steps_per_print": 1,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "resilience": {
            "enabled": True,
            "checkpoint_dir": ckpt_dir,
            "integrity": True,
            "integrity_peer_timeout_secs":
                _env_float("DS_INTEGRITY_PEER_TIMEOUT"),
            "hang_timeout_secs": _env_float("DS_WATCHDOG_SECS"),
        },
        "telemetry": {"enabled": True},
    }
    dataset = random_dataset(DATASET_SAMPLES, HIDDEN, seed=7)
    engine, _, loader, _ = deepspeed.initialize(
        model=SimpleModel(HIDDEN, nlayers=1), config=config, mesh=mesh,
        training_data=dataset, auto_resume=True)
    fresh = engine.global_steps == 0

    # start line: the seeded faults land on a rank's FIRST life, told by
    # ``fresh``.  Without it a rank that comes up after rank 0 committed
    # a step auto-resumes there, looks respawned and never takes its
    # fault.  Later lives find every first life's mark and pass at once.
    os.makedirs(out_dir, exist_ok=True)
    open(os.path.join(out_dir, f"ready-rank{rank}"), "w").close()
    deadline = time.monotonic() + 120.0
    while (sum(n.startswith("ready-rank") for n in os.listdir(out_dir))
           < _env_int("DS_NUM_PROCESSES", 1)
           and time.monotonic() < deadline):
        time.sleep(0.02)

    target = _env_int("DS_CHAOS_TARGET_RANK", -1)
    flip_step = _env_int("DS_CHAOS_BITFLIP_STEP")
    hang_step = _env_int("DS_CHAOS_HANG_STEP")
    step_sleep = _env_float("DS_STEP_SLEEP_SECS")
    monkey = ChaosMonkey(seed=_env_int("DS_CHAOS_SEED"))
    acc = engine.gradient_accumulation_steps()
    # pull index of the FIRST micro-batch of optimizer step k: the fault
    # lands before step k runs, on the first life only
    it = monkey.wrap_iter(
        iter(RepeatingLoader(loader)),
        bitflip_steps=[(flip_step - 1) * acc] if (flip_step and fresh)
        else [],
        bitflip_engine=engine,
        hang_steps=[(hang_step - 1) * acc] if (hang_step and fresh)
        else [],
        hang_secs=600.0,
        rank=rank, target_rank=target)

    life = "fresh" if fresh else f"resumed@{engine.global_steps}"
    log_path = os.path.join(out_dir, f"steps-rank{rank}-{life}.jsonl")
    loss = None          # a resumed-complete life never enters the loop
    try:
        with open(log_path, "a") as f:
            while engine.global_steps < TOTAL_STEPS:
                loss = engine.train_batch(it)
                if rank == 0:
                    engine.save_checkpoint(ckpt_dir, sync=True)
                f.write(json.dumps({
                    "step": engine.global_steps,
                    "loss": float(jax.device_get(loss)),
                    "samples": engine.global_samples}) + "\n")
                f.flush()
                if step_sleep:
                    time.sleep(step_sleep)
    except (FleetIntegrityError, TrainingDivergedError) as e:
        # the launcher's supervisor owns recovery: 87 = evict + resize,
        # 86 = poison (never respawned)
        sys.exit(e.exit_code)

    if rank == 0:
        if loss is not None:
            final_loss = float(jax.device_get(loss))
        else:
            # this life resumed already-complete (the previous life
            # died between its final commit and final.json): recover
            # the last trained loss from the step logs
            recs = []
            for name in os.listdir(out_dir):
                if name.startswith(f"steps-rank{rank}-"):
                    with open(os.path.join(out_dir, name)) as g:
                        recs += [json.loads(line) for line in g]
            final_loss = max(recs, key=lambda r: r["step"])["loss"]
        with open(os.path.join(out_dir, "final.json"), "w") as f:
            json.dump({"final_loss": final_loss,
                       "steps": engine.global_steps,
                       "samples": engine.global_samples}, f)
    engine.close()


if __name__ == "__main__":
    main()
