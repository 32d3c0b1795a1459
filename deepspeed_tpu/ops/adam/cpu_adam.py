"""DeepSpeedCPUAdam: the host (C++) optimizer for ZeRO-Offload.

TPU-native take on the reference's ``DeepSpeedCPUAdam``
(``deepspeed/ops/adam/cpu_adam.py:12``, kernel
``csrc/adam/cpu_adam.cpp:21-682``): the update arithmetic runs on the HOST
CPU in a compiled C++ kernel (``csrc/adam/cpu_adam.cpp`` here, JIT-built by
the op builder with g++ — the analog of the reference's ninja JIT load),
called from inside the engine's jitted step via ``jax.pure_callback``.
With ``cpu_offload`` the master/optimizer state already lives in host
memory, so the callback round-trip moves only the gradient — the
reference's async-grad-copy + CPU-step design (``stage2.py:793-900``).

Implements the same flat-optimizer protocol as :class:`FusedAdam`, with
identical numerics (bias correction, AdamW/L2 modes) so the two are
interchangeable per config.
"""

import ctypes
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map


class CPUAdamState(NamedTuple):
    exp_avg: jnp.ndarray
    exp_avg_sq: jnp.ndarray
    step: jnp.ndarray


_lib_cache = {}


def _load_kernel():
    """JIT-build csrc/adam/cpu_adam.cpp with g++ (cached .so)."""
    if "lib" in _lib_cache:
        return _lib_cache["lib"]
    from ..op_builder import jit_build

    so = jit_build("cpu_adam", ["csrc/adam/cpu_adam.cpp"])
    lib = ctypes.CDLL(so)
    f32p = ctypes.POINTER(ctypes.c_float)
    lib.ds_adam_step.argtypes = [f32p] * 7 + [
        ctypes.c_longlong] + [ctypes.c_float] * 7 + [ctypes.c_int]
    lib.ds_adam_step.restype = None
    _lib_cache["lib"] = lib
    return lib


def _host_adam(p, m, v, g, lr, beta1, beta2, wd, bc1, bc2, eps, adamw):
    lib = _load_kernel()
    p = np.ascontiguousarray(p, np.float32)
    m = np.ascontiguousarray(m, np.float32)
    v = np.ascontiguousarray(v, np.float32)
    g = np.ascontiguousarray(g, np.float32)
    p_out = np.empty_like(p)
    m_out = np.empty_like(m)
    v_out = np.empty_like(v)

    def ptr(a):
        return a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))

    lib.ds_adam_step(ptr(p_out), ptr(m_out), ptr(v_out), ptr(p), ptr(m),
                     ptr(v), ptr(g), p.size, float(lr), float(beta1),
                     float(beta2), float(eps), float(wd), float(bc1),
                     float(bc2), int(adamw))
    return p_out, m_out, v_out


class DeepSpeedCPUAdam:
    """Flat-space Adam whose arithmetic runs in the native host kernel."""

    name = "cpu_adam"

    def __init__(self, lr=1e-3, betas=(0.9, 0.999), eps=1e-8,
                 weight_decay=0.0, bias_correction=True, adamw_mode=True,
                 adam_w_mode=None, shard_axis=None, mesh=None, **_ignored):
        _load_kernel()  # fail fast if the toolchain is unavailable
        self.bias_correction = bias_correction
        # FusedAdam spells it adam_w_mode; accept both so the optimizers
        # are interchangeable per config (reference has the same dual
        # naming between FusedAdam and DeepSpeedCPUAdam)
        self.adamw_mode = adamw_mode if adam_w_mode is None else adam_w_mode
        # set by the engine under ZeRO: the flat buffers are sharded over
        # this mesh axis and each shard must call back independently
        self.shard_axis = shard_axis
        self.mesh = mesh
        self.eps = eps
        self.param_groups = [{
            "lr": lr,
            "betas": tuple(betas),
            "eps": eps,
            "weight_decay": weight_decay,
        }]
        self.defaults = {"lr": lr, "betas": tuple(betas)}

    def init_state(self, flat_master) -> CPUAdamState:
        z = jnp.zeros_like(flat_master)
        return CPUAdamState(exp_avg=z, exp_avg_sq=z,
                            step=jnp.asarray(0, jnp.int32))

    def hyperparams(self):
        g = self.param_groups[0]
        return {
            "lr": jnp.asarray(g["lr"], jnp.float32),
            "beta1": jnp.asarray(g["betas"][0], jnp.float32),
            "beta2": jnp.asarray(g["betas"][1], jnp.float32),
            "weight_decay": jnp.asarray(g["weight_decay"], jnp.float32),
        }

    def update(self, state: CPUAdamState, flat_master, flat_grads, hp,
               segments=None, segment_ids=None):
        step = state.step + 1
        if self.bias_correction:
            tf = step.astype(jnp.float32)
            bc1 = 1.0 - hp["beta1"] ** tf
            bc2 = 1.0 - hp["beta2"] ** tf
        else:
            bc1 = bc2 = jnp.asarray(1.0, jnp.float32)

        eps = self.eps
        adamw = self.adamw_mode

        def host_update(p, m, v, g, lr, b1, b2, wd, c1, c2):
            sds = (jax.ShapeDtypeStruct(p.shape, jnp.float32),) * 3

            def cb(p, m, v, g, lr, b1, b2, wd, c1, c2):
                return _host_adam(p, m, v, g, lr, b1, b2, wd, c1, c2, eps,
                                  adamw)

            return jax.pure_callback(cb, sds, p, m, v, g, lr, b1, b2, wd,
                                     c1, c2)

        g32 = jnp.asarray(flat_grads, jnp.float32)
        if self.shard_axis is not None:
            # ZeRO-sharded flat buffers: one callback PER SHARD inside
            # shard_map, so no cross-device gather happens and each host
            # only touches its addressable rows (the reference's per-rank
            # partitioned CPU step, stage2.py:1416-1427)
            from jax.sharding import PartitionSpec as P

            sharded = P(self.shard_axis)
            rep = P()
            # callbacks require FULLY-manual spmd: take every mesh axis
            # manual (buffers replicate over the non-data axes)
            new_p, new_m, new_v = shard_map(
                host_update, mesh=self.mesh,
                in_specs=(sharded, sharded, sharded, sharded,
                          rep, rep, rep, rep, rep, rep),
                out_specs=(sharded, sharded, sharded),
                axis_names=set(self.mesh.axis_names), check_vma=False)(
                flat_master, state.exp_avg, state.exp_avg_sq, g32,
                hp["lr"], hp["beta1"], hp["beta2"], hp["weight_decay"],
                bc1, bc2)
        else:
            new_p, new_m, new_v = host_update(
                flat_master, state.exp_avg, state.exp_avg_sq, g32,
                hp["lr"], hp["beta1"], hp["beta2"], hp["weight_decay"],
                bc1, bc2)
        return new_p, CPUAdamState(exp_avg=new_m, exp_avg_sq=new_v, step=step)
