"""Activation checkpointing (rematerialization) subsystem.

TPU-native re-design of ``deepspeed/runtime/activation_checkpointing/
checkpointing.py:282-663``.  The reference re-implements
``torch.utils.checkpoint`` with three memory knobs — partition saved
activations across model-parallel ranks (``:424-471``), offload them to CPU
(``PA_TO_CPU``), and contiguous preallocation — plus exact RNG replay.
Under JAX, recompute-in-backward is ``jax.checkpoint`` (RNG is functional,
so replay is free) and the knobs become *remat policies*:

- ``partition_activations`` → saved layer inputs carry a sharding
  constraint over the ``model`` mesh axis, so each MP rank stores 1/mp of
  every residual (gathered automatically when the backward recompute
  needs them).
- ``cpu_checkpointing``     → saved layer inputs are tagged with
  ``checkpoint_name`` and a ``save_and_offload_only_these_names`` policy
  moves them to ``pinned_host`` between forward and backward.
- ``number_checkpoints``    → checkpoint only that many evenly-spaced
  layers (the reference's ``num_checkpoints``); everything else stays
  un-remat'ed.

API parity: ``configure(...)`` + ``checkpoint(function, *args)`` mirror
``deepspeed.checkpointing.configure/checkpoint`` (reference
``__init__.py:25-27``); ``checkpoint_wrapper`` is the functional form the
models use.
"""

import jax

from .config import DeepSpeedActivationCheckpointingConfig

_CKPT_NAME = "ds_act_ckpt_input"

# module-level config, like the reference's checkpointing globals
_config = DeepSpeedActivationCheckpointingConfig({})


def configure(mpu_=None, deepspeed_config=None, partition_activations=None,
              contiguous_checkpointing=None, num_checkpoints=None,
              checkpoint_in_cpu=None, synchronize=None, profile=None,
              act_config=None):
    """Set the module config (reference ``checkpointing.configure``).
    Accepts either a parsed config object (engine path) or the reference's
    keyword overrides (client path)."""
    global _config
    if act_config is not None:
        _config = act_config
    if partition_activations is not None:
        _config.partition_activations = partition_activations
    if contiguous_checkpointing is not None:
        _config.contiguous_memory_optimization = contiguous_checkpointing
    if num_checkpoints is not None:
        _config.number_checkpoints = num_checkpoints
    if checkpoint_in_cpu is not None:
        _config.cpu_checkpointing = checkpoint_in_cpu
    if synchronize is not None:
        _config.synchronize_checkpoint_boundary = synchronize
    if profile is not None:
        _config.profile = profile
    return _config


def get_config():
    return _config


def is_configured():
    return _config is not None


def make_remat_policy(cfg=None, save_names=()):
    """The ``jax.checkpoint`` policy encoding the config's memory knobs.
    ``None`` means plain full remat (save only the layer boundary).
    ``save_names``: values the layer tagged with ``checkpoint_name`` that
    stay on the device instead of being recomputed (a kernel's outputs
    that cost more to make again than to keep)."""
    cfg = cfg or _config
    if cfg.cpu_checkpointing:
        return jax.checkpoint_policies.save_and_offload_only_these_names(
            names_which_can_be_saved=list(save_names),
            names_which_can_be_offloaded=[_CKPT_NAME],
            offload_src="device", offload_dst="pinned_host")
    if save_names:
        return jax.checkpoint_policies.save_only_these_names(*save_names)
    return None


def should_checkpoint_layer(index, num_layers, cfg=None):
    """``number_checkpoints`` spreads exactly k checkpoints evenly over the
    stack (reference ``num_checkpoints``); default: every layer."""
    cfg = cfg or _config
    k = cfg.number_checkpoints
    if not k or k >= num_layers:
        return True
    return index in {round(j * num_layers / k) for j in range(k)}


def _annotate(x, cfg):
    if not hasattr(x, "ndim"):
        return x
    if cfg.cpu_checkpointing:
        from jax.ad_checkpoint import checkpoint_name

        x = checkpoint_name(x, _CKPT_NAME)
    if cfg.partition_activations and x.ndim >= 2:
        from jax.sharding import PartitionSpec as P

        from ...parallel.mesh import get_current_mesh

        mesh = get_current_mesh()
        if mesh is not None and dict(zip(mesh.axis_names,
                                         mesh.devices.shape)).get("model", 1) > 1:
            # shard the saved residual's second dim (sequence for [b,s,h])
            # across the model axis — each MP rank stores 1/mp
            # (reference partition_activations, checkpointing.py:424-471)
            spec = [None] * x.ndim
            spec[1] = "model"
            x = jax.lax.with_sharding_constraint(x, P(*spec))
    return x


def checkpoint_wrapper(fn, cfg=None, argnums=None, save_names=()):
    """Wrap a layer-apply function in config-driven rematerialization;
    ``save_names`` as in :func:`make_remat_policy`.

    The offload/partition annotations apply to the layer's *activations*,
    never its weights (annotating parameters would stream every weight to
    host / re-shard it inside the remat region).  By default only
    bare-array positional args are annotated — the ``fn(params_pytree,
    x, rng)`` convention our layers use — or pass ``argnums`` to select
    explicitly.
    """
    cfg = cfg or _config

    def annotated(*args, **kwargs):
        args = tuple(
            _annotate(a, cfg)
            if ((argnums is None and hasattr(a, "ndim"))
                or (argnums is not None and i in argnums))
            else a
            for i, a in enumerate(args))
        return fn(*args, **kwargs)

    policy = make_remat_policy(cfg, save_names)
    if policy is not None:
        return jax.checkpoint(annotated, policy=policy)
    return jax.checkpoint(annotated)


def checkpoint(function, *args):
    """Reference-API immediate form (``deepspeed.checkpointing.checkpoint``)."""
    return checkpoint_wrapper(function)(*args)
