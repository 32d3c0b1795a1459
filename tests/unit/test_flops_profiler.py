"""Flops profiler: exact counts on known-FLOPs modules, control-flow
handling, per-scope attribution, engine integration (reference
``tests/unit/test_flops_profiler.py``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as deepspeed
from deepspeed_tpu.parallel import make_mesh
from deepspeed_tpu.profiling.flops_profiler import (count_fn_flops,
                                                    get_model_profile,
                                                    params_count)

from .simple_model import SimpleModel, base_config, random_batches

HIDDEN = 16


def test_matmul_exact_count():
    B, K, N = 8, 32, 64
    x = jnp.ones((B, K))
    w = jnp.ones((K, N))
    flops, _ = count_fn_flops(lambda a, b: a @ b, x, w)
    assert flops == 2 * B * K * N


def test_grad_counts_backward_too():
    """Training FLOPs come from the traced backward, not a 3x heuristic:
    d(xW) needs two more matmuls (dx = gW^T, dW = x^T g)."""
    B, K, N = 4, 8, 16
    x = jnp.ones((B, K))
    w = jnp.ones((K, N))

    def loss(w):
        return jnp.sum(x @ w)

    fwd, _ = count_fn_flops(loss, w)
    bwd, _ = count_fn_flops(jax.grad(loss), w)
    assert bwd >= fwd + 2 * B * K * N - 2 * B * N  # two extra matmuls


def test_scan_multiplies_by_length():
    K = 16
    w = jnp.ones((K, K))

    def scanned(x):
        def body(c, _):
            return c @ w, None
        out, _ = jax.lax.scan(body, x, None, length=10)
        return out

    one, _ = count_fn_flops(lambda x: x @ w, jnp.ones((2, K)))
    ten, _ = count_fn_flops(scanned, jnp.ones((2, K)))
    assert ten == 10 * one


def test_named_scope_attribution():
    K = 32
    w1 = jnp.ones((K, K))
    w2 = jnp.ones((K, 2 * K))

    def fn(x):
        with jax.named_scope("small"):
            a = x @ w1
        with jax.named_scope("big"):
            b = a @ w2
        return jnp.sum(b)

    flops, by_scope = count_fn_flops(fn, jnp.ones((4, K)))
    small = sum(v for k, v in by_scope.items() if "small" in k)
    big = sum(v for k, v in by_scope.items() if "big" in k)
    assert small == 2 * 4 * K * K
    assert big == 2 * 4 * K * 2 * K


def test_get_model_profile_simple_model():
    model = SimpleModel(HIDDEN, nlayers=2)
    batch = random_batches(1, 8, HIDDEN, seed=0)[0]
    params = model.init(jax.random.PRNGKey(0))
    flops, macs, n_params = get_model_profile(model=model, batch=batch,
                                              params=params,
                                              print_profile=False)
    assert n_params == params_count(params)
    assert flops > 0 and macs == flops // 2
    ftrain, _, _ = get_model_profile(model=model, batch=batch, params=params,
                                     train=True, print_profile=False)
    assert ftrain > flops  # backward included


def test_engine_profiler_wiring(cpu_devices):
    config = base_config(flops_profiler={"enabled": True, "profile_step": 2})
    mesh = make_mesh({"data": 8}, devices=cpu_devices[:8])
    engine, *_ = deepspeed.initialize(model=SimpleModel(HIDDEN, nlayers=2),
                                      config=config, mesh=mesh)
    assert engine.flops_profiler is not None
    batch = random_batches(1, engine.train_micro_batch_size_per_gpu() * 8,
                           HIDDEN, seed=0)[0]
    for _ in range(3):
        engine.train_batch(iter([batch]))
    prof = engine.flops_profiler.profile
    assert prof is not None, "profiler did not run at profile_step"
    assert prof.flops > 0
    assert prof.params == params_count(engine._param_template)


def test_conv_flops_exact_count():
    import jax.lax as lax

    B, C, H, W, O, K = 2, 3, 8, 8, 4, 3
    x = jnp.ones((B, C, H, W))
    w = jnp.ones((O, C, K, K))

    def conv(x, w):
        return lax.conv_general_dilated(
            x, w, window_strides=(1, 1), padding="SAME",
            dimension_numbers=("NCHW", "OIHW", "NCHW"))

    flops, _ = count_fn_flops(conv, x, w)
    # 2 * output elements * kernel taps per output channel
    assert flops == 2 * (B * O * H * W) * (C * K * K)


def test_while_loop_counts_one_iteration():
    """Data-dependent trip counts are invisible to the jaxpr walk: one
    iteration is counted (the documented reference-parity caveat)."""
    K = 16
    w = jnp.ones((K, K))

    def looped(x):
        def cond(c):
            return jnp.sum(c[0]) < 1e9

        def body(c):
            return (c[0] @ w, c[1] + 1)

        out, _ = jax.lax.while_loop(cond, body, (x, 0))
        return out

    one, _ = count_fn_flops(lambda x: x @ w, jnp.ones((2, K)))
    loop, _ = count_fn_flops(looped, jnp.ones((2, K)))
    assert one <= loop < 2 * one + K * K  # body once, not N times


def test_cond_counts_hot_branch():
    K = 32
    w_small = jnp.ones((K, K))
    w_big = jnp.ones((K, 4 * K))

    def f(x, pred):
        return jax.lax.cond(pred,
                            lambda a: jnp.sum(a @ w_big),
                            lambda a: jnp.sum(a @ w_small), x)

    big, _ = count_fn_flops(lambda x: jnp.sum(x @ w_big),
                            jnp.ones((4, K)))
    both, _ = count_fn_flops(f, jnp.ones((4, K)), True)
    assert both >= big  # the hot (max-flops) branch is what counts


def test_backend_cost_analysis_returns_dict():
    from deepspeed_tpu.profiling.flops_profiler import profiler as prof_mod

    fn = jax.jit(lambda a, b: a @ b)
    cost = prof_mod.backend_cost_analysis(fn, jnp.ones((8, 8)),
                                          jnp.ones((8, 8)))
    assert isinstance(cost, dict)  # {} when the backend offers none


def test_flops_profile_wall_and_mfu():
    from deepspeed_tpu.profiling.flops_profiler.profiler import FlopsProfile
    from deepspeed_tpu.profiling.utilization import (chip_peak_tflops,
                                                     chip_specs)

    prof = FlopsProfile(flops=2 * 10 ** 12, macs=10 ** 12, params=1000,
                        wall_ms=100.0)
    assert prof.achieved_tflops() == 20.0

    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

    assert chip_peak_tflops(Dev) == 197.0
    assert prof.mfu(Dev) == 20.0 / 197.0
    # a device that is not a TPU has no peak, so no MFU...
    assert prof.mfu(jax.devices()[0]) is None
    # ...and a TPU the table does not know is an error, never a default
    Dev.device_kind = "TPU v99"
    with pytest.raises(ValueError, match="TPU v99"):
        prof.mfu(Dev)
    with pytest.raises(ValueError, match="TPU v99"):
        chip_specs("TPU v99")
    assert chip_specs("cpu")["peak_tflops"] > 0  # static analysers only
    assert FlopsProfile(1, 0, 1).achieved_tflops() is None
