"""Compile-time scaling of the streamed-offload update: unrolled vs scan.

The round-5 capacity ceiling was COMPILE WALL TIME: the unrolled
chunk-streamed update lowers one full update pipeline per chunk, so
program size grows linearly in chunk count and compile time grows
super-linearly (round 5: gpt2-xl, 37 chunks, ~35 min; 2.7B never
finished in 30 min).  The uniform-chunk scan update
(``runtime/zero/stream.py``, ``"offload_uniform_chunks"``) traces the
chunk body once — this script measures both forms' lower+compile wall
at growing chunk counts over a FIXED model, so the scaling (not the
absolute seconds, which are backend-dependent) is the receipt.

Runs on any backend: on CPU (no pinned_host memory space) it forces the
in-jit program structure (DS_OFFLOAD_FORCE_INJIT) with placements
compiled as no-ops — program SHAPE, and therefore compile-cost scaling,
is what this benchmark is about.

Usage: python examples/bench_compile_scaling.py [chunk_mb ...]
"""

import os
import sys
import time

if os.environ.get("JAX_PLATFORMS", "") in ("", "cpu"):
    os.environ.setdefault("DS_OFFLOAD_FORCE_INJIT", "1")
# a process-local cache would hide recompiles of the SAME program; each
# (mode, chunk_mb) program here is distinct, but keep runs hermetic
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import jax
import jax.numpy as jnp

import deepspeed_tpu as deepspeed
from deepspeed_tpu.parallel import make_mesh

HIDDEN = int(os.environ.get("SCALING_HIDDEN", "1024"))
LAYERS = int(os.environ.get("SCALING_LAYERS", "32"))


class _Stack:
    """Minimal linear stack conforming to the engine model contract."""

    def init(self, rng):
        params = {}
        for i in range(LAYERS):
            k, rng = jax.random.split(rng)
            params[f"l{i}"] = {"w": jax.random.normal(
                k, (HIDDEN, HIDDEN), jnp.float32) * 0.02}
        return params

    def apply(self, params, batch, rng=None, train=True, **kw):
        h = batch
        for i in range(LAYERS):
            h = jnp.tanh(h @ params[f"l{i}"]["w"])
        return jnp.mean(h ** 2)


def measure(uniform, chunk_mb):
    mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
    engine, *_ = deepspeed.initialize(
        model=_Stack(), mesh=mesh,
        config={"train_batch_size": 4, "steps_per_print": 10 ** 9,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
                "zero_optimization": {"stage": 2, "cpu_offload": True,
                                      "offload_chunk_mb": chunk_mb,
                                      "offload_uniform_chunks": uniform},
                # compiles ARE the measurement here — never cache them
                "compilation": {"cache": False},
                "bf16": {"enabled": True}})
    rows = engine.segments.rows
    chunks = -(-rows * 4096 // (chunk_mb << 20))
    flat_g = jnp.zeros(engine.segments.shape, jnp.float32)
    hp = engine._device_hyperparams()
    t0 = time.perf_counter()
    lowered = engine._apply_fn.lower(
        engine.state["master"], engine.state["opt"], engine.state["scale"],
        engine.state["skipped"], flat_g, hp, None)
    t_lower = time.perf_counter() - t0
    t0 = time.perf_counter()
    lowered.compile()
    t_compile = time.perf_counter() - t0
    hlo_lines = lowered.as_text().count("\n")
    return chunks, hlo_lines, t_lower, t_compile


def main():
    chunk_mbs = [int(a) for a in sys.argv[1:]] or [16, 4, 1]
    print(f"model: {LAYERS}x{HIDDEN}^2 linear stack, "
          f"state rows vary with chunk alignment; backend="
          f"{jax.devices()[0].platform}")
    print(f"{'mode':>9} {'chunk_mb':>8} {'chunks':>6} {'hlo_lines':>9} "
          f"{'lower_s':>8} {'compile_s':>9}")
    for uniform in (False, True):
        for cmb in chunk_mbs:
            chunks, lines, tl, tc = measure(uniform, cmb)
            mode = "scan" if uniform else "unrolled"
            print(f"{mode:>9} {cmb:>8} {chunks:>6} {lines:>9} "
                  f"{tl:>8.2f} {tc:>9.2f}", flush=True)


if __name__ == "__main__":
    main()
