"""Compilation subsystem: persistent XLA cache + compile telemetry.

Every mechanism is backend-agnostic and CI-checked here: config
parsing/validation, the ONE rule for where the cache lives
(``JAX_COMPILATION_CACHE_DIR`` where set, and then nothing is set in
code; else ``<checkout>/.jax_cache``; an explicit ``cache_dir`` the one
override), the TWO-FRESH-SUBPROCESS warm-start roundtrip, and the
jax.monitoring -> TelemetryManager bridge.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

from deepspeed_tpu.runtime.compilation import (CompileStats,
                                               DeepSpeedCompilationConfig,
                                               configure_persistent_cache,
                                               install_compile_telemetry,
                                               uninstall_compile_telemetry)

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))


@pytest.fixture
def cache_knobs():
    """Snapshot/restore the process-global jax cache config + env (these
    tests deliberately flip them; the rest of the suite must keep the
    conftest-configured warm cache)."""
    old = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_persistent_cache_min_compile_time_secs,
           jax.config.jax_persistent_cache_min_entry_size_bytes,
           os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    yield
    jax.config.update("jax_compilation_cache_dir", old[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", old[1])
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", old[2])
    if old[3] is None:
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    else:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = old[3]


# ---------------------------------------------------------------- config
def test_config_defaults_and_validation():
    cfg = DeepSpeedCompilationConfig({})
    assert cfg.cache is True and cfg.cache_dir == ""
    assert cfg.min_entry_size_bytes == 0 and cfg.min_compile_secs == 0.0
    cfg = DeepSpeedCompilationConfig(
        {"compilation": {"cache": True, "cache_dir": "/x",
                         "min_entry_size_bytes": 4096,
                         "min_compile_secs": 1.5}})
    assert cfg.cache is True and cfg.cache_dir == "/x"
    assert cfg.min_entry_size_bytes == 4096 and cfg.min_compile_secs == 1.5
    # true or false, nothing else: 0/1 and strings are rejected, not
    # coerced (an explicit disable must never read as enabled)
    for bad in ("yes", "auto", 0, 1):
        with pytest.raises(ValueError):
            DeepSpeedCompilationConfig({"compilation": {"cache": bad}})
    with pytest.raises(ValueError):
        DeepSpeedCompilationConfig(
            {"compilation": {"min_entry_size_bytes": -1}})
    with pytest.raises(ValueError):
        DeepSpeedCompilationConfig({"compilation": {"min_compile_secs": -1}})


def test_compilation_block_in_dsc4xx_schema():
    """The dslint config-schema extractor knows the new block: a typo'd
    sub-key is flagged with a suggestion (DSC402 machinery)."""
    from deepspeed_tpu.tools.dslint.schema import validate_config_dict

    issues = validate_config_dict(
        {"compilation": {"cache": True, "cach_dir": "/x"}})
    assert len(issues) == 1
    assert issues[0].section == "compilation"
    assert issues[0].suggestion == "cache_dir"
    assert not validate_config_dict(
        {"compilation": {"cache": True, "cache_dir": "/x",
                         "min_entry_size_bytes": 0,
                         "min_compile_secs": 0.5}})


# ---------------------------------------------------------------- policy
@pytest.fixture
def cache_dir_updates(monkeypatch):
    """Every ``jax.config.update("jax_compilation_cache_dir", ...)`` made
    while the fixture is live (the call still goes through)."""
    calls = []
    real = jax.config.update

    def spy(name, value):
        if name == "jax_compilation_cache_dir":
            calls.append(value)
        return real(name, value)

    monkeypatch.setattr(jax.config, "update", spy)
    return calls


def _train_engine(config=None):
    import deepspeed_tpu as deepspeed
    from deepspeed_tpu.parallel import make_mesh

    from .simple_model import SimpleModel, base_config

    engine, *_ = deepspeed.initialize(
        model=SimpleModel(hidden_dim=16),
        config=dict(base_config(train_batch_size=8), **(config or {})),
        mesh=make_mesh({"data": 1}, devices=jax.devices("cpu")[:1]))
    return engine


def _serve_engine(config=None):
    from deepspeed_tpu.inference import InferenceEngine
    from deepspeed_tpu.models import GPT2Config, GPT2LMHeadTPU

    model = GPT2LMHeadTPU(GPT2Config(
        vocab_size=64, hidden_size=32, num_layers=1, num_heads=2,
        max_position_embeddings=32, embd_dropout=0.0, attn_dropout=0.0,
        resid_dropout=0.0))
    return InferenceEngine(
        model, model.init(jax.random.PRNGKey(0)),
        config=dict({"inference": {"kv_block_size": 8, "kv_blocks": 8,
                                   "max_batch_slots": 2, "max_seq_len": 32,
                                   "prefill_buckets": [16]}},
                    **(config or {})))


@pytest.mark.parametrize("make_engine", [_train_engine, _serve_engine])
def test_env_var_set_means_no_directory_set_in_code(
        cache_knobs, cache_dir_updates, monkeypatch, tmp_path, make_engine):
    """With JAX_COMPILATION_CACHE_DIR set jax uses it natively: neither
    engine sets a directory in code, not even for an explicit
    ``cache_dir`` in its config."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    for config in ({}, {"compilation": {"cache_dir": str(tmp_path / "x")}}):
        engine = make_engine(config)
        assert engine._compile_cache_dir == str(tmp_path / "env")
    assert cache_dir_updates == []
    assert not (tmp_path / "x").exists()


@pytest.mark.parametrize("make_engine", [_train_engine, _serve_engine])
def test_env_var_unset_means_checkout_jax_cache_from_any_cwd(
        cache_knobs, monkeypatch, tmp_path, make_engine):
    """Unset, both engines resolve ``<checkout>/.jax_cache`` — a fixed
    path next to the package, whatever the working directory (the path is
    part of every cache key, so one that moves never hits)."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.chdir(tmp_path)
    engine = make_engine()
    want = os.path.join(REPO, ".jax_cache")
    assert engine._compile_cache_dir == want
    assert jax.config.jax_compilation_cache_dir == want
    assert os.listdir(tmp_path) == []  # nothing under the cwd


def test_configure_disabled_touches_nothing(cache_knobs, cache_dir_updates,
                                            monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    cfg = DeepSpeedCompilationConfig({"compilation": {"cache": False}})
    assert configure_persistent_cache(cfg) is None
    assert cache_dir_updates == []


def test_explicit_cache_dir_is_the_one_override(cache_knobs, monkeypatch,
                                                tmp_path):
    """With the variable unset an explicit ``compilation.cache_dir`` wins
    over the default, with the block's thresholds — and nothing is
    exported: a child resolves its directory by the same rule."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    cfg = DeepSpeedCompilationConfig(
        {"compilation": {"cache_dir": str(tmp_path / "mine"),
                         "min_compile_secs": 0.25}})
    got = configure_persistent_cache(cfg)
    assert got == str(tmp_path / "mine") and os.path.isdir(got)
    assert jax.config.jax_compilation_cache_dir == got
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.25
    assert "JAX_COMPILATION_CACHE_DIR" not in os.environ


def test_configure_leaves_an_already_resolved_cache_alone(
        cache_knobs, cache_dir_updates, monkeypatch):
    """The harness (or an earlier engine) already pointed jax at the
    directory the rule resolves: a later engine changes nothing, so the
    harness keeps its own thresholds."""
    from deepspeed_tpu.runtime.compilation.cache import default_cache_dir

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax.config.update("jax_compilation_cache_dir", default_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    del cache_dir_updates[:]
    got = configure_persistent_cache(DeepSpeedCompilationConfig({}))
    assert got == default_cache_dir()
    assert cache_dir_updates == []
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.5


# ------------------------------------------------- fresh-process roundtrip
_ROUNDTRIP = r"""
import json, os, sys, time
t0 = time.perf_counter()
import numpy as np, jax
import deepspeed_tpu as deepspeed
from deepspeed_tpu.runtime.compilation import CompileStats
from deepspeed_tpu.parallel import make_mesh

stats = CompileStats()


class Stack:
    def init(self, rng):
        import jax.numpy as jnp
        ks = jax.random.split(rng, 4)
        return {f"l{i}": jax.random.normal(ks[i], (64, 64)) * 0.1
                for i in range(4)}

    def apply(self, params, batch, rng=None, train=True, **kw):
        import jax.numpy as jnp
        h, y = batch
        for i in range(4):
            h = jnp.tanh(h @ params[f"l{i}"])
        return jnp.mean((h - y) ** 2)


mesh = make_mesh({"data": 1}, devices=jax.devices("cpu")[:1])
engine, *_ = deepspeed.initialize(
    model=Stack(), mesh=mesh,
    config={"train_batch_size": 8, "steps_per_print": 10 ** 9,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "compilation": {"cache_dir": sys.argv[1]}})
rng = np.random.default_rng(0)
b = (rng.normal(size=(8, 64)).astype(np.float32),
     rng.normal(size=(8, 64)).astype(np.float32))
loss = engine.train_batch(iter([b]))
assert np.isfinite(float(np.asarray(jax.device_get(loss))))
out = stats.as_dict()
out["wall_secs"] = round(time.perf_counter() - t0, 3)
print("ROUNDTRIP " + json.dumps(out))
"""


def _roundtrip_run(cache_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH",
                                                             ""))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, "-c", _ROUNDTRIP, str(cache_dir)],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    for line in proc.stdout.splitlines():
        if line.startswith("ROUNDTRIP "):
            return json.loads(line[len("ROUNDTRIP "):])
    raise AssertionError(f"no ROUNDTRIP line in: {proc.stdout[-2000:]}")


def test_two_fresh_subprocess_cache_roundtrip(tmp_path):
    """THE warm-start receipt, process-boundary honest: a second fresh
    process against the populated cache loads its programs (cache hits,
    near-zero cold-compile wall) instead of recompiling them."""
    cache_dir = tmp_path / "xla_cache"
    cold = _roundtrip_run(cache_dir)
    assert cold["compile_cache_misses"] > 0, cold
    assert cold["compile_seconds_cold"] > 0, cold
    assert os.listdir(cache_dir), "cache dir not populated"
    warm = _roundtrip_run(cache_dir)
    assert warm["compile_cache_hits"] >= cold["compile_cache_misses"], (
        cold, warm)
    assert warm["compile_cache_misses"] == 0, warm
    # measurably faster: the backend-compile wall actually paid must
    # collapse (wall-clock totals are import-dominated on CPU; the
    # compile split is the robust signal — and what PERF.md records)
    assert warm["compile_seconds_cold"] <= cold["compile_seconds_cold"] * 0.2, (
        cold, warm)


# ------------------------------------------------------ telemetry bridge
def test_compile_telemetry_bridge(cache_knobs, tmp_path):
    """A backend compile becomes a ``compile`` event + histogram sample +
    trace span; persistent-cache traffic becomes hit/miss counters.
    Everything is host-side listener work — no engine, no device sync."""
    import jax.numpy as jnp

    from deepspeed_tpu.telemetry.config import DeepSpeedTelemetryConfig
    from deepspeed_tpu.telemetry.manager import TelemetryManager

    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    manager = TelemetryManager(DeepSpeedTelemetryConfig(
        {"telemetry": {"enabled": True, "run_dir": str(tmp_path / "run"),
                       "trace": True}}), rank=0)
    install_compile_telemetry(manager)
    try:
        fn = jax.jit(lambda x: jnp.sin(x) * jnp.float32(41.5))
        fn(jnp.ones((33, 5))).block_until_ready()
        assert manager.registry.counter("compile/cache_miss").value >= 1
        assert manager.registry.counter("compile/programs").value >= 1
        # same lowered program, fresh executable cache -> persistent hit
        jax.clear_caches()
        fn = jax.jit(lambda x: jnp.sin(x) * jnp.float32(41.5))
        fn(jnp.ones((33, 5))).block_until_ready()
        assert manager.registry.counter("compile/cache_hit").value >= 1
    finally:
        uninstall_compile_telemetry(manager)
        manager.close()
    events = [json.loads(l) for l in
              open(tmp_path / "run" / "events-rank0.jsonl")]
    compiles = [e for e in events if e["type"] == "compile"]
    assert compiles and all(
        e["data"]["duration_secs"] > 0 for e in compiles)
    trace = (tmp_path / "run" / "trace-rank0.json").read_text()
    assert '"compile"' in trace

    # unsubscribed: further compiles must not increment this manager
    before = manager.registry.counter("compile/programs").value
    jax.jit(lambda x: x - jnp.float32(17.25))(
        jnp.ones((7, 3))).block_until_ready()
    assert manager.registry.counter("compile/programs").value == before


def test_compile_stats_collector(cache_knobs, tmp_path):
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "c"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    import jax.numpy as jnp

    stats = CompileStats()
    jax.jit(lambda x: jnp.cos(x) + jnp.float32(3.125))(
        jnp.ones((11, 9))).block_until_ready()
    stats.close()
    d = stats.as_dict()
    assert d["compile_cache_misses"] >= 1
    assert d["compile_seconds_cold"] > 0
    assert d["compile_programs"] >= 1


def test_compile_stats_count_traces_and_lowerings_by_program():
    """A function jitted at two static arguments is traced twice (what a
    retrace costs even when the persistent cache then hits): the count
    and the seconds are the program's, and a jitted callee's seconds are
    in ``trace_secs`` once."""
    import functools

    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnums=1)
    def twice_traced_program(x, n):
        for _ in range(4):
            x = jnp.tanh(x) * n + jnp.sum(x)
        return x

    stats = CompileStats()
    for n in (3, 5, 3):                    # the third call is a cache hit
        twice_traced_program(jnp.ones((7, 5)), n).block_until_ready()
    stats.close()
    assert stats.traces_by_program["twice_traced_program"] == 2
    own = stats.trace_secs_by_program["twice_traced_program"]
    assert 0 < own <= stats.trace_secs
    # jnp.tanh and jnp.sum are jitted themselves and traced inside
    assert stats.traces_by_program["tanh"] >= 2
    assert stats.trace_secs < sum(stats.trace_secs_by_program.values())
    assert stats.lower_secs > 0
    # closed: a later trace moves nothing
    before = stats.trace_secs
    twice_traced_program(jnp.ones((7, 5)), 7).block_until_ready()
    assert stats.trace_secs == before
