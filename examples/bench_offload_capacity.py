"""ZeRO-Offload capacity headline: largest model trainable on ONE chip.

The reference's ZeRO-Offload claim is "10× bigger models on one GPU —
13B params on a single V100-32GB" (``docs/_posts/2020-09-09-
ZeRO-Offload.md:10``).  This measures the TPU framework's analog on the
single v5e (16 GB HBM): walk GPT-2-family configs upward, try a few
training steps with ``cpu_offload`` off vs on, record the largest config
that trains and the offload step-time tax.

Each trial runs in a FRESH SUBPROCESS: compiled executables and buffers
from a previous trial linger in-process (observed: a config that OOMs
after prior same-process trials trains fine alone), so isolation is the
only way to get truthful capacity numbers.  All trials share one
persistent XLA compile cache (each resolves the same directory by the
rule in ``runtime/compilation/cache.py``), so a re-run — or a retry of a flaked trial — warm-starts its programs;
each trial prints its cold/warm compile-wall split.

Rows past gpt2-xl ride the round-6 O(1)-compile configuration: the
uniform-chunk scan update ("offload_uniform_chunks": auto engages past
24 chunks) keeps program size constant in chunk count — the round-5
blocker at 2.7B was >30 min of compile wall for the unrolled chunk
programs, not memory.

Round 12 adds an **overlap mode** (``overlap`` argument): A/B the
double-buffered chunk pipeline (``offload_overlap`` on vs off) on the
gpt2-large offload row and emit ONE ``bench_schema``-validated JSON
record as the last line — ``offload_gpt2_large_ms_per_step`` (the
serialized control), ``offload_gpt2_large_overlap_ms_per_step`` (the
headline; target ≤ ~0.5 s/step on the chip, not measured), plus both
schedules' static exposed-wire receipts so the bench JSON alone shows
the exposure drop.  On a non-TPU backend the same harness path runs
end-to-end at toy geometry under ``DS_OFFLOAD_FORCE_INJIT`` and the
record carries ``note: "dryrun"`` — a CPU box proves the plumbing, only
the chip gives the milliseconds.

Usage: python examples/bench_offload_capacity.py [quick|overlap [quick]]
"""

import json
import os
import subprocess
import sys

SEQ = 1024
BATCH = int(os.environ.get("CAP_BATCH", "4"))
STEPS = int(os.environ.get("CAP_STEPS", "6"))
TIMEOUT = int(os.environ.get("CAP_TIMEOUT", "3600"))

# (name, hidden, layers, heads) — params ≈ 12·L·h² + vocab·h
LADDER = [
    ("gpt2-medium-0.35B", 1024, 24, 16),
    ("gpt2-large-0.77B", 1280, 36, 20),
    ("gpt2-1.0B", 1408, 40, 22),
    ("gpt2-xl-1.5B", 1600, 48, 25),
    ("gpt2-2.7B", 2560, 32, 32),
    ("gpt2-4.2B", 3072, 36, 32),
    ("gpt2-6.7B", 4096, 32, 32),
]

_TRIAL = r"""
import time, numpy as np, jax
from deepspeed_tpu.runtime.compilation import CompileStats
import deepspeed_tpu as deepspeed
from deepspeed_tpu.models import GPT2Config, GPT2LMHeadTPU
from deepspeed_tpu.parallel import make_mesh
import os
stats = CompileStats()
h = int(os.environ["T_H"]); L = int(os.environ["T_L"])
heads = int(os.environ["T_HEADS"]); off = os.environ["T_OFF"] == "1"
batch = int(os.environ["T_B"]); steps = int(os.environ["T_S"])
cfg = GPT2Config(hidden_size=h, num_layers=L, num_heads=heads,
                 max_position_embeddings=1024, embd_dropout=0.0,
                 attn_dropout=0.0, resid_dropout=0.0,
                 remat=True, loss_chunk=256)
mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
model = GPT2LMHeadTPU(cfg)
og = os.environ.get("T_OG") == "1"
zero = {"stage": 2, "cpu_offload": off, "offload_gradients": og and off}
gmb = int(os.environ.get("T_GMB", "0"))
if gmb:
    # manual escape hatch only: the coordinator auto-derives the group
    # layout by capping total buffer COUNT since round 6 (the round-5
    # many-buffer AOT crash mode; gpt2-xl needed a manual 3584 then)
    zero["offload_group_mb"] = gmb
sdt = os.environ.get("T_SDT", "")
if sdt:
    # reduced-precision host state ("bf16"/"fp16"): halves state wire
    zero["offload_state_dtype"] = sdt
ov = os.environ.get("T_OV", "")
cfg_extra = {}
if ov:
    # overlap A/B mode: pin the issue schedule explicitly and enable
    # the comm ledger so the trial can print the static exposed-wire
    # receipt next to the measured milliseconds
    zero["offload_overlap"] = ov == "on"
    cfg_extra["profiling"] = {"comm_ledger": True}
cmb = os.environ.get("T_CMB", "")
if cmb:
    zero["offload_chunk_mb"] = int(cmb)
engine, *_ = deepspeed.initialize(model=model, mesh=mesh,
    config={"train_batch_size": batch, "steps_per_print": 10 ** 9,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
            "zero_optimization": zero,
            "bf16": {"enabled": True}, **cfg_extra})
rng = np.random.default_rng(0)
b = {"input_ids": rng.integers(0, cfg.vocab_size,
                               size=(batch, 1024)).astype(np.int32)}
# TWO fenced warmups: the engine compiles a second program on step 1
for _ in range(2):
    loss = engine.train_batch(iter([b]))
    float(np.asarray(jax.device_get(loss)))
t0 = time.perf_counter()
for _ in range(steps):
    loss = engine.train_batch(iter([b]))
v = float(np.asarray(jax.device_get(loss)))
dt = (time.perf_counter() - t0) / steps
assert np.isfinite(v)
s = stats.as_dict()
print(f"CAP_COMPILE cold={s['compile_seconds_cold']} "
      f"warm={s['compile_seconds_warm']} hits={s['compile_cache_hits']} "
      f"misses={s['compile_cache_misses']}")
if off:
    print(f"CAP_STATE dtype={engine.host_state_dtype()} "
          f"bytes_per_step={engine.host_state_bytes_per_step()} "
          f"groups={len(engine.flat.host_group_bounds or ((0, 0),))}")
if ov:
    rcpt = engine.overlap_receipt() or {}
    sched = engine.host_stream_schedule() or {}
    print("CAP_OVERLAP " + __import__("json").dumps({
        "overlap": sched.get("overlap"),
        "prefetch_depth": sched.get("prefetch_depth"),
        "chunks": sched.get("chunks"),
        "exposed_wire_seconds": rcpt.get("exposed_wire_seconds"),
        "overlap_fraction": rcpt.get("overlap_fraction"),
        "host_state_bytes_per_step": engine.host_state_bytes_per_step(),
    }))
print(f"CAP_RESULT {dt * 1e3:.0f}")
"""


def param_count(h, L, vocab=50257, pos=SEQ):
    return 12 * L * h * h + (vocab + pos) * h + 2 * h


def try_step(offload, hidden, layers, heads, offload_grads=False,
             params=0, extra_env=None):
    env = dict(os.environ, T_H=str(hidden), T_L=str(layers),
               T_HEADS=str(heads), T_OFF="1" if offload else "0",
               T_B=str(BATCH), T_S=str(STEPS),
               T_OG="1" if offload_grads else "0")
    env.update(extra_env or {})
    # no T_GMB default: the coordinator's buffer-count cap derives the
    # round-5 3584 layout (and beyond) automatically; export T_GMB to
    # force a manual group size, T_SDT=bf16 for reduced host state
    try:
        proc = subprocess.run([sys.executable, "-u", "-c", _TRIAL], env=env,
                              capture_output=True, text=True,
                              timeout=TIMEOUT)
    except subprocess.TimeoutExpired:
        return False, f"TIMEOUT ({TIMEOUT // 60} min)", "", None
    compile_line = ""
    overlap = None
    result = None
    for line in proc.stdout.splitlines():
        if line.startswith("CAP_COMPILE "):
            compile_line = line[len("CAP_COMPILE "):]
        if line.startswith("CAP_STATE "):
            compile_line = (compile_line + "  " if compile_line
                            else "") + line[len("CAP_STATE "):]
        if line.startswith("CAP_OVERLAP "):
            try:
                overlap = json.loads(line[len("CAP_OVERLAP "):])
            except ValueError:
                overlap = None
        if line.startswith("CAP_RESULT "):
            result = float(line.split()[1]) / 1e3
    if result is not None:
        return True, result, compile_line, overlap
    err = proc.stdout[-300:] + proc.stderr[-300:]
    oom = ("RESOURCE_EXHAUSTED" in err or "memory space hbm" in err
           or "Out of memory" in err or "ResourceExhausted" in err)
    return False, ("OOM" if oom else err.replace("\n", " ")[-200:]), \
        compile_line, overlap


def _backend_platform():
    """Default jax backend of a fresh subprocess (the parent stays
    jax-free so every trial keeps its isolation)."""
    try:
        proc = subprocess.run(
            [sys.executable, "-c",
             "import jax; print(jax.default_backend())"],
            capture_output=True, text=True, timeout=120)
        return proc.stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def overlap_mode():
    """A/B the overlapped vs serialized chunk schedule and emit the
    bench record (see module docstring).  The LAST stdout line is the
    JSON record — drivers capture it like every other bench."""
    platform = _backend_platform()
    dryrun = platform != "tpu"
    if dryrun:
        # toy geometry through the identical harness path: fresh
        # subprocess, forced in-jit streaming, chunked scan, receipts
        h, L, heads = 256, 4, 4
        extra = {"T_CMB": "1", "T_SDT": "bf16",
                 "DS_OFFLOAD_FORCE_INJIT": "1",
                 "T_B": os.environ.get("CAP_BATCH", "1"),
                 "T_S": os.environ.get("CAP_STEPS", "2")}
    else:
        h, L, heads = 1280, 36, 20  # gpt2-large, the headline row
        extra = {"T_SDT": "bf16"}
    record = {"metric": "offload_overlap", "device": platform,
              "offload_gpt2_large_params_b": round(
                  param_count(h, L) / 1e9, 3)}
    if dryrun:
        record["offload_gpt2_large_overlap_note"] = (
            "dryrun: non-TPU backend, toy geometry (hidden "
            f"{h}, {L} layers) under DS_OFFLOAD_FORCE_INJIT — harness "
            "receipt only; the ms/step target needs the chip")
    rows = {}
    for tag, ov in (("off", "off"), ("on", "on")):
        ok, info, compile_line, overlap = try_step(
            True, h, L, heads, extra_env={**extra, "T_OV": ov})
        suffix = f"  [{compile_line}]" if compile_line else ""
        if not ok:
            print(f"[overlap={tag}] FAIL {info}{suffix}", flush=True)
            record[f"offload_gpt2_large_overlap_error" if ov == "on"
                   else "offload_gpt2_large_error"] = str(info)[:300]
            continue
        rows[tag] = (info, overlap or {})
        print(f"[overlap={tag}] OK {info * 1e3:.0f} ms/step "
              f"{json.dumps(overlap)}{suffix}", flush=True)
    if "off" in rows:
        ms, ov_d = rows["off"]
        record["offload_gpt2_large_ms_per_step"] = round(ms * 1e3, 3)
        if ov_d.get("exposed_wire_seconds") is not None:
            record["offload_gpt2_large_exposed_wire_seconds"] = float(
                ov_d["exposed_wire_seconds"])
            record["offload_gpt2_large_overlap_fraction"] = float(
                ov_d["overlap_fraction"])
    if "on" in rows:
        ms, ov_d = rows["on"]
        record["offload_gpt2_large_overlap_ms_per_step"] = round(
            ms * 1e3, 3)
        for src, dst in (("exposed_wire_seconds",
                          "offload_gpt2_large_overlap_exposed_wire_seconds"),
                         ("overlap_fraction",
                          "offload_gpt2_large_overlap_overlap_fraction")):
            if ov_d.get(src) is not None:
                record[dst] = float(ov_d[src])
        if ov_d.get("host_state_bytes_per_step") is not None:
            record["offload_gpt2_large_overlap_host_state_bytes_per_step"] \
                = int(ov_d["host_state_bytes_per_step"])
    # schema check (fail-soft: drift reports to stderr, the record
    # always prints — the standing measurement rule)
    try:
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        from deepspeed_tpu.tools.bench_schema import validate_record

        for problem in validate_record(record):
            print(f"bench-schema: {problem}", file=sys.stderr)
    except Exception as e:  # pragma: no cover
        print(f"bench-schema unavailable: {e!r}", file=sys.stderr)
    print(json.dumps(record))
    return record


def main():
    if "overlap" in sys.argv[1:]:
        overlap_mode()
        return
    quick = "quick" in sys.argv[1:]
    ladder = LADDER[:3] if quick else LADDER
    # three modes: device-resident, offload (state only), offload+grads
    # (offload_gradients — the capacity configuration: bf16 params are
    # the only per-param device cost)
    modes = (("device", False, False), ("offload", True, False),
             ("offload+grads", True, True))
    results = {}
    for mode, offload, og in modes:
        for name, h, L, heads in ladder:
            n = param_count(h, L)
            ok, info, compile_line, _ = try_step(offload, h, L, heads,
                                                 offload_grads=og,
                                                 params=n)
            suffix = f"  [{compile_line}]" if compile_line else ""
            if ok:
                print(f"[{mode}] {name}: OK  {info * 1e3:.0f} ms/step "
                      f"({BATCH * SEQ / info:.0f} tok/s, {n / 1e9:.2f}B)"
                      f"{suffix}", flush=True)
                results[(mode, name)] = info
            else:
                print(f"[{mode}] {name}: FAIL {info} ({n / 1e9:.2f}B)"
                      f"{suffix}", flush=True)
                break  # ladder is monotone in memory need

    order = [name for name, *_ in LADDER]
    print("\nsummary:")
    for mode, *_ in modes:
        ok_names = [n for n in order if (mode, n) in results]
        if ok_names:
            largest = ok_names[-1]
            print(f"  {mode}: largest trainable = {largest} "
                  f"({results[(mode, largest)] * 1e3:.0f} ms/step)")
        else:
            print(f"  {mode}: nothing trained")


if __name__ == "__main__":
    main()
