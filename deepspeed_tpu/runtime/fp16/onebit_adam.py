"""1-bit Adam on the flat-parameter optimizer protocol.

TPU-native re-design of the reference ``deepspeed/runtime/fp16/
onebit_adam.py:18-374`` (``OnebitAdam``): a two-phase Adam variant for
bandwidth-bound (DCN) data parallelism —

1. **Warmup** (``step < freeze_step``): ordinary dense Adam; both moments
   update normally (reference ``:262-304``) and gradients are synchronized
   densely by the engine's standard data-parallel reduction.
2. **Compression stage** (``step >= freeze_step``): the variance ``v`` is
   frozen and the dense gradient all-reduce is *eliminated* — the only
   data-axis communication is the packed 1-bit sign of each rank's local
   momentum plus one scale per chunk, with worker/server error feedback
   (reference ``:118-214``, ``Compressed_Allreduce``; engine hook
   ``enable_backward_allreduce = False`` at ``:372``).  Wire payload is
   1/32 of fp32.

Execution model: XLA cannot branch around collectives on a traced step
counter, but the freeze transition is host-known — so the engine compiles
TWO programs and switches between them at ``freeze_step`` (the analog of
the reference's Python-level phase switch).  The warmup program is the
engine's standard fused step; the compressed program
(:meth:`OnebitAdam.build_compressed_step`) wraps the whole
micro-batch-scan + momentum-sync + update in one ``shard_map`` over the
``data`` axis, where each rank back-propagates only its local batch shard
(no gradient psum) and the momentum consensus comes from
:func:`~deepspeed_tpu.comm.compression.compressed_allreduce`.

Like the reference (``:230-260``), no bias correction is applied and
weight decay is L2-style, added to the update after the momentum term.
Restrictions (asserted): ZeRO stage 0 (as in the reference's
``ZERO_SUPPORTED_OPTIMIZERS``), no fp16 dynamic loss scaling in the
compressed phase (use bf16), no gradient clipping post-freeze.
"""

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ...comm.compression import compressed_allreduce
from ...parallel.mesh import DATA_AXIS


class OnebitAdamState(NamedTuple):
    exp_avg: jnp.ndarray        # m, f32[rows, lanes], consensus (replicated)
    exp_avg_sq: jnp.ndarray     # v, f32[rows, lanes], frozen post-freeze
    worker_error: jnp.ndarray   # f32[dp, n_pad] per-rank residual ('data'-sharded)
    server_error: jnp.ndarray   # f32[dp, n_pad/dp] per-rank chunk residual
    step: jnp.ndarray           # i32 scalar


class OnebitAdam:
    """Flat-space 1-bit Adam (reference ``onebit_adam.py:18``)."""

    name = "onebit_adam"

    def __init__(self, deepspeed=None, lr=1e-3, freeze_step=100000,
                 betas=(0.9, 0.999), eps=1e-8, weight_decay=0.0,
                 cuda_aware=False, **_ignored):
        assert deepspeed is not None, "OnebitAdam needs the engine (mesh access)"
        zero_stage = getattr(deepspeed, "zero_stage", 0)
        assert zero_stage == 0, (
            f"OneBitAdam is incompatible with ZeRO (stage={zero_stage}); the "
            "reference has the same restriction (ZERO_SUPPORTED_OPTIMIZERS)")
        self._engine = deepspeed
        self.freeze_step = int(freeze_step)
        self.eps = eps
        self.dp = deepspeed.dp_world_size
        self.param_groups = [{
            "lr": lr,
            "betas": tuple(betas),
            "eps": eps,
            "weight_decay": weight_decay,
        }]
        self.defaults = {"lr": lr, "betas": tuple(betas)}

    # error-buffer geometry: flat size padded so every rank serves an equal
    # chunk of whole bytes (stage 0 does not pad rows to the dp degree);
    # the alignment itself is owned by comm/compression.padded_size —
    # compressed_allreduce pads/trims the DATA buffer internally, the
    # optimizer only allocates the persistent error buffers at the
    # padded size
    def _padded_n(self, flat_shape):
        from ...comm.compression import padded_size

        return padded_size(int(np.prod(flat_shape)), self.dp)

    def init_state(self, flat_master) -> OnebitAdamState:
        z = jnp.zeros_like(flat_master)
        n_pad = self._padded_n(flat_master.shape)
        return OnebitAdamState(
            exp_avg=z, exp_avg_sq=z,
            worker_error=jnp.zeros((self.dp, n_pad), jnp.float32),
            server_error=jnp.zeros((self.dp, n_pad // self.dp), jnp.float32),
            step=jnp.asarray(0, jnp.int32))

    def state_shardings(self, mesh, master_sharding, replicated):
        """Per-leaf shardings for the engine (error buffers are per-rank
        along the data axis; moments follow the master)."""
        return OnebitAdamState(
            exp_avg=master_sharding, exp_avg_sq=master_sharding,
            worker_error=NamedSharding(mesh, P(DATA_AXIS, None)),
            server_error=NamedSharding(mesh, P(DATA_AXIS, None)),
            step=replicated)

    def hyperparams(self):
        g = self.param_groups[0]
        return {
            "lr": jnp.asarray(g["lr"], jnp.float32),
            "beta1": jnp.asarray(g["betas"][0], jnp.float32),
            "beta2": jnp.asarray(g["betas"][1], jnp.float32),
            "weight_decay": jnp.asarray(g["weight_decay"], jnp.float32),
        }

    def update(self, state: OnebitAdamState, flat_master, flat_grads, hp,
               segments=None, segment_ids=None):
        """Warmup-phase (dense) update: plain Adam without bias correction,
        error-feedback buffers untouched (reference ``:262-304``; the
        reference skips bias correction in both phases too).  The engine
        switches to the compressed program at ``freeze_step``.

        Sharp edge (inherent to the algorithm, reference included): the
        frozen ``exp_avg_sq`` is whatever accumulated by ``freeze_step`` —
        with β₂ = 0.999 that is only ``1 − 0.999^t`` of the true second
        moment, so freezing early makes every compressed-phase update
        ``~1/sqrt(1 − β₂^t)`` times too hot and training can diverge.
        Choose ``freeze_step`` so β₂-accumulation has saturated (the
        reference's recipes freeze after ~23k steps), or lower β₂.
        """
        lr, beta1, beta2, wd = hp["lr"], hp["beta1"], hp["beta2"], hp["weight_decay"]
        g = jnp.asarray(flat_grads, jnp.float32)
        p = flat_master
        m = beta1 * state.exp_avg + (1.0 - beta1) * g
        v = beta2 * state.exp_avg_sq + (1.0 - beta2) * (g * g)
        update = m / (jnp.sqrt(v) + self.eps) + wd * p
        return p - lr * update, OnebitAdamState(
            exp_avg=m, exp_avg_sq=v, worker_error=state.worker_error,
            server_error=state.server_error, step=state.step + 1)

    # ------------------------------------------------------------------
    # compressed-phase program
    # ------------------------------------------------------------------
    def build_compressed_step(self, mesh, loss_fn, flat_coordinator,
                              param_template, compute_dtype, param_shardings,
                              unpack_fn, acc_steps, base_rng, master_sharding,
                              opt_shardings, extra_signature=()):
        """Compile the post-freeze train step: grads stay rank-local, the
        momentum consensus is the 1-bit collective, and the dense gradient
        all-reduce never happens.  Signature mirrors the engine's fused
        ``train_step`` so the engine can switch host-side."""
        eps = self.eps
        segments = flat_coordinator.segments

        def compressed_step(master, opt_state, scale_state, skipped, ustep,
                            params, packed, unpack_spec, hp, segment_ids,
                            extra):
            lr, beta1, wd = hp["lr"], hp["beta1"], hp["weight_decay"]

            def body(packed_local, m, v, we, se, master_, params_):
                # we: [1, n_pad] local slice → [n_pad]; se: [1, n_pad/dp]
                we, se = we[0], se[0]
                batches = unpack_fn(packed_local, unpack_spec)
                rank = jax.lax.axis_index(DATA_AXIS)
                rng = jax.random.fold_in(
                    jax.random.fold_in(base_rng, ustep), rank)

                def local_grads(batch_i, key):
                    def local_loss(p):
                        loss = loss_fn(p, batch_i, rng=key, train=True, **extra)
                        return loss.astype(jnp.float32) / acc_steps

                    loss, grads = jax.value_and_grad(local_loss)(params_)
                    return loss * acc_steps, flat_coordinator.flatten_grads(grads)

                def micro(carry, xs):
                    acc, i = carry
                    loss, fg = local_grads(
                        jax.tree_util.tree_map(lambda x: x[i], batches),
                        jax.random.fold_in(rng, i))
                    return (acc + fg, i + 1), loss

                if acc_steps == 1:
                    one = jax.tree_util.tree_map(lambda x: x[0], batches)
                    loss, flat_g = local_grads(one, rng)
                    losses = loss[None]
                else:
                    (flat_g, _), losses = jax.lax.scan(
                        micro, (jnp.zeros(segments.shape, jnp.float32),
                                jnp.asarray(0, jnp.int32)),
                        jnp.arange(acc_steps))

                # rank-local momentum; THE data-axis sync is 1-bit
                # (compressed_allreduce pads to 8*world alignment and
                # trims internally — real flat sizes just work)
                m_local = beta1 * m + (1.0 - beta1) * flat_g
                m_bar, new_we, new_se = compressed_allreduce(
                    m_local.reshape(-1), we, se, DATA_AXIS)
                m_bar = m_bar.reshape(segments.shape)

                update = m_bar / (jnp.sqrt(v) + eps) + wd * master_
                new_master = master_ - lr * update
                new_params = flat_coordinator.unflatten_params(
                    new_master, param_template, compute_dtype, constrain=False)
                mean_loss = jax.lax.pmean(jnp.mean(losses), DATA_AXIS)
                return (mean_loss, new_master, m_bar, new_we[None],
                        new_se[None], new_params)

            rep = P()
            (loss, new_master, m_bar, new_we, new_se, new_params) = \
                shard_map(
                    body, mesh=mesh,
                    in_specs=(P(None, DATA_AXIS, None), rep, rep,
                              P(DATA_AXIS, None), P(DATA_AXIS, None), rep, rep),
                    out_specs=(rep, rep, rep, P(DATA_AXIS, None),
                               P(DATA_AXIS, None),
                               jax.tree_util.tree_map(lambda _: rep,
                                                      param_template)),
                    axis_names={DATA_AXIS}, check_vma=False)(
                    packed, opt_state.exp_avg, opt_state.exp_avg_sq,
                    opt_state.worker_error, opt_state.server_error,
                    master, params)

            new_opt = OnebitAdamState(
                exp_avg=m_bar, exp_avg_sq=opt_state.exp_avg_sq,
                worker_error=new_we, server_error=new_se,
                step=opt_state.step + 1)
            overflow = jnp.asarray(False)
            gnorm = jnp.asarray(0.0, jnp.float32)
            return (loss, new_master, new_opt, scale_state, skipped,
                    ustep + jnp.uint32(1), overflow, gnorm, new_params)

        return jax.jit(
            compressed_step,
            static_argnums=(7,),
            donate_argnums=(0, 1, 5),
            out_shardings=(None, master_sharding, opt_shardings, None, None,
                           None, None, None, param_shardings))
