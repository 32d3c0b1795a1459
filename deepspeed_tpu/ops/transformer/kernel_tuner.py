"""Runtime block autotuning for the flash-attention kernels.

The reference bakes a GEMM autotuner into kernel setup — every transformer
kernel build runs a small search over algorithms and caches the winner
(``/root/reference/csrc/includes/gemm_test.h``).  This is the TPU analog
for the Pallas flash kernels: the hand-calibrated ``_auto_blocks``
heuristic stays authoritative for the shapes it was measured on (the
"anchored" regimes below — those never re-tune, so two cold machines
always run them with the same blocks), and any OTHER shape gets a cached
first-use micro-search over a small block-geometry candidate set.

Search cost is one kernel compile per candidate (~4-6 candidates) the
first time a new (seq, kv_len, head_dim, causal, dropout) shape is seen
on a TPU backend; winners persist to ``flash_blocks.json`` in the compile
cache directory (``runtime/compilation/cache.py`` has the one rule for
where that is), beside the compiled programs they shaped, so every later
process that finds the programs finds the geometry too.  On a machine
with no such file two cold runs may pick different blocks for an
un-anchored shape; ``flash_attention`` logs the geometry of every
distinct kernel call so that a run says which it used.

Measurement discipline: candidates run under one ``lax.scan`` inside a
single jit (dispatch cost is identical across candidates, so it cancels
in the ranking), with three interleaved repeats and min-aggregation.

Knobs: ``DS_FLASH_AUTOTUNE=0`` disables the search (pure heuristic),
``=1`` forces tuning even for anchored shapes, unset/``auto`` tunes only
un-anchored shapes on TPU backends.
"""

import json
import logging
import os
import time

import jax
import jax.numpy as jnp

_memory_cache = {}
_disk_loaded = False

# Tuner algorithm revision, part of every cache key: winners persist to
# disk indefinitely, so a ranking fixed by a later tuner (candidate set,
# timing discipline, screening) must INVALIDATE cached pre-fix winners —
# keying by shape+device alone let mis-ranked geometries outlive the
# tuner that produced them (VERDICT r5).  Bump this when the search
# changes in any way that can alter a winner; stale-version entries are
# simply ignored (and rewritten on the next tune of that shape).
#
# v2: version-carrying keys; retires v1 entries ranked before the
# interleaved-repeat/min-aggregation discipline carried its own version.
TUNER_VERSION = 2


def _cache_path():
    from ...runtime.compilation.cache import active_cache_dir

    return os.path.join(active_cache_dir(), "flash_blocks.json")


def _mode():
    return os.environ.get("DS_FLASH_AUTOTUNE", "auto")


def anchored(s, kv_len, d, causal):
    """Shapes the hand calibration covers (PERF.md measured anchors):
    d=64 self-attention at power-of-two-ish lengths where _auto_blocks'
    choice was A/B-measured on chip.  Everything else is fair game for
    the runtime search."""
    if d != 64 or kv_len != s:
        return False
    if causal and s <= 1024:
        return True  # single-tile path, measured best (round 4b)
    return s in (128, 256, 512, 1024, 2048, 4096, 8192, 16384, 32768)


def _key(s, kv_len, d, causal, dropout, device_kind=""):
    # device_kind in the key: a geometry tuned on a v5e must not be
    # silently reused on a v4/v5p (different VMEM/MXU/bandwidth).
    # TUNER_VERSION in the key: a geometry ranked by an older tuner
    # must not be silently reused by a newer one.
    dk = device_kind.replace("|", "_").replace(" ", "_")
    return (f"v{TUNER_VERSION}|{dk}|s{s}|kv{kv_len}|d{d}|c{int(causal)}"
            f"|p{int(dropout > 0)}")


def _load_disk():
    global _disk_loaded
    if _disk_loaded:
        return
    _disk_loaded = True
    try:
        with open(_cache_path()) as f:
            _memory_cache.update(json.load(f))
    except Exception:  # dslint: disable=DSE502 -- cache file absent/corrupt on first run; tuner just re-measures
        pass


def _save_disk():
    try:
        path = _cache_path()
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(_memory_cache, f, indent=1, sort_keys=True)
    except Exception:  # dslint: disable=DSE502 -- read-only FS etc.; in-memory cache still works
        pass


def candidates(s, kv_len, d, causal):
    """Small but diverse block-geometry set.  VMEM cap mirrors
    _auto_blocks: block_k * d <= 128K elements."""
    kmax_el = (128 * 1024) // max(d, 1)
    qs = [c for c in (1024, 512, 256, 128) if c <= s and s % c == 0]
    ks = [c for c in (2048, 1024, 512, 256, 128)
          if c <= min(kv_len, kmax_el) and kv_len % c == 0]
    out = []
    for q in qs[:3]:
        for k in ks:
            if causal and k > q:
                continue  # measured: straddling tiles lose (PERF.md)
            out.append((q, k))
    # single-tile candidate where it fits VMEM (the round-4b winner
    # regime, generalized to other d)
    if s == kv_len and s <= kmax_el and s % 128 == 0 and (s, s) not in out:
        out.append((s, s))
    # dedupe preserving order, cap the search
    seen, uniq = set(), []
    for c in out:
        if c not in seen:
            seen.add(c)
            uniq.append(c)
    return uniq[:6]


def tune(s, kv_len, d, causal, dropout, flash_fn, heuristic, bh=8):
    """Search block geometries for one shape; returns (block_q, block_k).

    ``flash_fn(q, k, v, block_q=, block_k=, causal=, dropout_seed=,
    dropout_rate=)`` is the kernel entry (passed in to avoid a circular
    import); ``heuristic`` is the fallback/first candidate."""
    if _mode() == "0":
        return heuristic
    try:
        dev = jax.devices()[0]
        if dev.platform != "tpu":
            return heuristic  # search is only meaningful on the target HW
        kind = getattr(dev, "device_kind", "tpu")
    except Exception:
        return heuristic
    key = _key(s, kv_len, d, causal, dropout, kind)
    _load_disk()
    if key in _memory_cache:
        return tuple(_memory_cache[key])
    if _mode() != "1" and anchored(s, kv_len, d, causal):
        return heuristic

    cands = candidates(s, kv_len, d, causal)
    if heuristic not in cands:
        cands.insert(0, heuristic)
    logging.getLogger("DeepSpeedTPU").info(
        "flash-attention autotune: first use of shape s=%d kv=%d d=%d "
        "causal=%s — compiling and timing %d block geometries (one-time; "
        "cached at %s; DS_FLASH_AUTOTUNE=0 disables)",
        s, kv_len, d, causal, len(cands), _cache_path())

    kq = jax.random.PRNGKey(0)
    q = jax.random.normal(kq, (1, s, bh, d), jnp.bfloat16)
    k = jax.random.normal(kq, (1, kv_len, bh, d), jnp.bfloat16)
    v = jax.random.normal(kq, (1, kv_len, bh, d), jnp.bfloat16)
    seed = jnp.zeros((2,), jnp.int32) if dropout else None

    def make_run(bq, bk):
        def loss(q_, k_, v_):
            out = flash_fn(q_, k_, v_, causal=causal, block_q=bq,
                           block_k=bk, dropout_seed=seed,
                           dropout_rate=dropout)
            return jnp.sum(out.astype(jnp.float32))

        @jax.jit
        def run(q_, k_, v_):
            def body(c, _):
                l, grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(
                    q_ + c.astype(jnp.bfloat16), k_, v_)
                # fold the GRADIENTS into the carry too: an unused grads
                # output would be dead-code-eliminated and the candidates
                # ranked (and compile-screened) on the forward alone
                gtok = sum(g.reshape(-1)[0].astype(jnp.float32)
                           for g in grads)
                return c + (l + gtok) * 1e-30, None
            c, _ = jax.lax.scan(body, jnp.float32(0.0), None, length=8)
            return c
        return run

    runners = {}
    for bq, bk in cands:
        run = make_run(bq, bk)
        try:
            run(q, k, v).block_until_ready()  # compile + warm
            runners[(bq, bk)] = run
        except Exception:
            continue  # candidate doesn't compile at this shape — skip
    if not runners:
        return heuristic

    # INTERLEAVED repeats with min-aggregation (back-to-back repeats
    # let one load spike mis-rank a whole candidate)
    results = {c: [] for c in runners}
    for _ in range(3):
        for c, run in runners.items():
            t0 = time.perf_counter()
            float(jax.device_get(run(q, k, v)))
            results[c].append(time.perf_counter() - t0)

    best = min(results, key=lambda c: min(results[c]))
    _memory_cache[key] = list(best)
    _save_disk()
    return best
