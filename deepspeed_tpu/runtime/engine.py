"""DeepSpeed-TPU training engine.

TPU-native re-design of ``deepspeed/runtime/engine.py`` (DeepSpeedEngine,
reference ``:95-1573``).  The public API is kept — ``initialize()`` returns
``(engine, optimizer, dataloader, lr_scheduler)``; the engine exposes
``forward/backward/step``, ``train_batch``, ``save_checkpoint`` /
``load_checkpoint``, and the config accessor methods — but the execution
model is rebuilt around XLA:

- The train step is three jitted programs: ``_fwd_bwd`` (loss + grads, with
  the loss pre-scaled by loss-scale / grad-accumulation), ``_accum`` (flat
  gradient accumulation), and ``_apply`` (unscale → overflow check → clip →
  fused optimizer update on the flat fp32 master).  There are no backward
  hooks (reference ``stage2.py:583``) — gradient partitioning is expressed
  as sharding annotations and XLA GSPMD inserts reduce-scatter/all-gather
  collectives and overlaps them with compute.
- ZeRO stages are *sharding policies of the flat parameter space* over the
  ``data`` mesh axis (see ``zero/`` package), not runtime bucketing
  (reference ``stage1.py``/``stage2.py``).
- Mixed precision is bf16-first; fp16 + in-jit dynamic loss scaling is kept
  for config parity (reference ``fp16/fused_optimizer.py``).
- DP gradient averaging (reference ``allreduce_gradients``/
  ``buffered_allreduce_fallback``, ``engine.py:836-1246``) falls out of
  batch sharding: the model's mean loss over the globally-sharded batch
  makes XLA emit the gradient all-reduce (or reduce-scatter under ZeRO≥2).

Model contract: ``model.init(rng) -> params`` and
``model.apply(params, batch, rng=key, train=bool, **kw) -> scalar loss`` in
training (any pytree output for ``train=False``).  A bare callable
``loss_fn(params, batch, rng, **kw)`` plus explicit ``model_parameters`` is
also accepted.  Optional ``model.partition_specs(mesh) -> pytree of
PartitionSpec`` enables tensor parallelism over the ``model`` axis.
"""

import json
import os
import pickle
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import checkpoint as ckpt
from ..checkpoint import CheckpointManager, capture_engine_snapshot, drain_inflight
from ..checkpoint.snapshot import ensure_owned
from ..checkpoint.writer import CheckpointCorruptionError, CheckpointError
from ..ops.adam.fused_adam import FusedAdam
from ..ops.lamb.fused_lamb import FusedLamb
from ..ops.op_common import LANES
from ..parallel.mesh import (DATA_AXIS, MeshGrid, make_mesh,
                             mesh_axis_sizes, set_current_mesh)
from ..telemetry import events as TEL
from ..utils.distributed import init_distributed
from ..utils.logging import log_dist, logger
from ..utils.timer import SynchronizedWallClockTimer, ThroughputTimer
from . import constants as C
from .config import DeepSpeedConfig
from .dataloader import DeepSpeedDataLoader, RepeatingLoader
from .fp16.loss_scaler import DynamicScaleState, update_scale_state
from .lr_schedules import SCHEDULE_CLASSES
from .progressive_layer_drop import ProgressiveLayerDrop
from .utils import tree_path_key

def _pack_batches(micro_batches):
    """Stack ``grad_acc`` micro-batch pytrees and pack all leaves into ONE
    host array per dtype, laid out ``[acc, batch, columns]``.

    Every host→device transfer is a dispatch of its own, so a batch pytree
    of N leaves costs N of them per step.  Packing collapses it to one
    transfer per dtype (usually one total);
    the jitted step unpacks with free slices/reshapes.  Returns
    ``(packed: {dtype_str: np.ndarray}, spec)`` where ``spec`` is hashable
    and passed as a static arg.
    """
    stacked = jax.tree_util.tree_map(
        lambda *xs: np.stack([np.asarray(x) for x in xs]), *micro_batches)
    leaves, treedef = jax.tree_util.tree_flatten(stacked)
    assert leaves, "empty batch"
    bsz = leaves[0].shape[1]
    cols = {}
    entries = []
    for leaf in leaves:
        assert leaf.ndim >= 2 and leaf.shape[1] == bsz, (
            f"batch leaves must be [batch, ...] with a common batch dim; "
            f"got stacked shape {leaf.shape} vs batch {bsz}")
        key = str(leaf.dtype)
        tail = leaf.shape[2:]
        ncols = int(np.prod(tail)) if tail else 1
        parts = cols.setdefault(key, [])
        off = sum(p.shape[2] for p in parts)
        parts.append(leaf.reshape(leaf.shape[0], bsz, ncols))
        entries.append((key, off, ncols, tuple(tail)))
    packed = {k: np.concatenate(v, axis=2) for k, v in cols.items()}
    spec = (treedef, tuple(entries), bsz)
    return packed, spec


def _unpack_batches(packed, spec):
    """Inverse of :func:`_pack_batches`, traced inside the fused step.
    The batch dim is taken from the array, not the spec: inside shard_map
    the caller sees only its local 1/dp slice of the batch."""
    treedef, entries, _ = spec
    leaves = []
    for key, off, ncols, tail in entries:
        arr = packed[key][:, :, off:off + ncols]
        leaves.append(arr.reshape((arr.shape[0], arr.shape[1]) + tail))
    return jax.tree_util.tree_unflatten(treedef, leaves)


# layout names live with the checkpoint subsystem; aliased here for
# back-compat with older imports
MODEL_STATES_NPZ = ckpt.MODEL_STATES_NPZ
OPTIM_STATES_NPZ = ckpt.OPTIM_STATES_NPZ
META_JSON = ckpt.META_JSON
CLIENT_STATE_PKL = ckpt.CLIENT_STATE_PKL
LATEST_FILE = ckpt.LATEST_FILE


def initialize(args=None,
               model=None,
               optimizer=None,
               model_parameters=None,
               training_data=None,
               lr_scheduler=None,
               mpu=None,
               dist_init_required=None,
               collate_fn=None,
               config=None,
               config_params=None,
               mesh=None,
               auto_resume=False,
               aot_plan=False):
    """Initialize the DeepSpeed-TPU engine (reference ``__init__.py:50-139``).

    Returns ``(engine, optimizer, training_dataloader, lr_scheduler)``.

    With ``auto_resume=True`` the engine restores the latest committed
    checkpoint from ``resilience.checkpoint_dir`` via the atomic
    ``latest`` pointer (warn-and-start-fresh when none exists) — the
    respawn half of the resilience contract: a launcher restarting a
    crashed/hung job re-runs the same script and lands on the last good
    step instead of step 0.

    With ``aot_plan=True`` the engine builds and jits its step programs
    but never materializes device-resident module params — the AOT
    capacity planner's mode (``profiling/capacity.py``): lower + compile
    the train step and read ``memory_analysis()`` without running it.
    """
    log_dist("DeepSpeed-TPU initialize", ranks=[0])
    from .pipe.module import PipelineModule

    if isinstance(model, PipelineModule):
        from .pipe.engine import PipelineEngine

        engine = PipelineEngine(args=args, model=model, optimizer=optimizer,
                                model_parameters=model_parameters,
                                training_data=training_data, lr_scheduler=lr_scheduler,
                                dist_init_required=dist_init_required,
                                collate_fn=collate_fn, config=config,
                                config_params=config_params, mesh=mesh)
    else:
        engine = DeepSpeedEngine(args=args, model=model, optimizer=optimizer,
                                 model_parameters=model_parameters,
                                 training_data=training_data, lr_scheduler=lr_scheduler,
                                 mpu=mpu, dist_init_required=dist_init_required,
                                 collate_fn=collate_fn, config=config,
                                 config_params=config_params, mesh=mesh,
                                 aot_plan=aot_plan)
    if auto_resume:
        load_dir = engine.resilience_config.checkpoint_dir
        if load_dir is None:
            logger.warning(
                "auto_resume: resilience.checkpoint_dir is not configured; "
                "starting fresh (set it so respawned jobs resume)")
        else:
            path, _ = engine.load_checkpoint(load_dir)
            if path is None:
                log_dist(f"auto_resume: no committed checkpoint under "
                         f"{load_dir}; starting fresh", ranks=[0])
            else:
                log_dist(f"auto_resume: resumed from {path}", ranks=[0])
    return engine, engine.optimizer, engine.training_dataloader, engine.lr_scheduler


class DeepSpeedEngine:
    """Central training engine (reference ``engine.py:95``)."""

    def __init__(self, args=None, model=None, optimizer=None, model_parameters=None,
                 training_data=None, lr_scheduler=None, mpu=None,
                 dist_init_required=None, collate_fn=None, config=None,
                 config_params=None, mesh=None, dont_build_steps=False,
                 aot_plan=False):
        assert model is not None, "deepspeed.initialize requires a model"
        if dist_init_required or dist_init_required is None:
            init_distributed()

        # -- config resolution (reference engine.py:460-470) --
        config = config if config is not None else config_params
        if config is None and args is not None:
            config = getattr(args, "deepspeed_config", None) or getattr(
                args, "deepscale_config", None)
        assert config is not None, (
            "DeepSpeed requires --deepspeed_config, a config dict, or config_params")

        self.mpu = mpu
        self._config_source = config

        # -- mesh (replaces process-group setup, reference engine.py:521-538) --
        if mesh is not None:
            self.mesh = mesh
            mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
            world_size = int(np.prod(mesh.devices.shape)) // max(
                1, mesh_shape.get("model", 1) * mesh_shape.get("pipe", 1)
                * mesh_shape.get("seq", 1) * mesh_shape.get("expert", 1))
            self._config = DeepSpeedConfig(config, mpu, world_size=world_size)
        else:
            self._config = DeepSpeedConfig(config, mpu)
            self.mesh = make_mesh(self._config.mesh_config)
        set_current_mesh(self.mesh)
        shape = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        self.dp_world_size = shape.get("data", 1)
        self.mp_world_size = shape.get("model", 1)
        assert self.dp_world_size == self._config.world_size, (
            f"mesh data axis {self.dp_world_size} != config world size "
            f"{self._config.world_size}")
        self.grid = MeshGrid(self.mesh)
        self.world_size = self.grid.world_size

        # -- compilation subsystem (runtime/compilation): persistent XLA
        # compile cache, BEFORE the first jit of this engine (model.init,
        # the flatten, the fused step) so warm-start processes — bench
        # reruns, --max-restarts respawns, auto_resume restarts — load
        # every one of those programs instead of recompiling them --
        from .compilation import configure_persistent_cache

        self.compilation_config = self._config.compilation_config
        self._compile_cache_dir = configure_persistent_cache(
            self.compilation_config)

        # -- precision --
        if self._config.fp16_enabled:
            self.compute_dtype = jnp.float16
        elif self._config.bf16_enabled:
            self.compute_dtype = jnp.bfloat16
        else:
            self.compute_dtype = jnp.float32
        self.dynamic_loss_scale_enabled = (
            self._config.fp16_enabled and self._config.loss_scale == 0)
        self.static_loss_scale = (self._config.loss_scale
                                  if self._config.fp16_enabled and self._config.loss_scale != 0
                                  else 1.0)

        # -- resilience (deepspeed_tpu/resilience): the config is needed
        # here because _build_step_functions folds the guard's non-finite
        # detection into the compiled step; the guard/watchdog objects are
        # built after the checkpoint subsystem below --
        self.resilience_config = self._config.resilience_config

        # -- activation checkpointing (reference checkpointing.configure;
        # VERDICT: config must drive remat, not per-model flags) --
        from .activation_checkpointing import checkpointing as ds_checkpointing
        from .activation_checkpointing.config import ACT_CHKPT

        if ACT_CHKPT in self._config._param_dict:
            ds_checkpointing.configure(
                act_config=self._config.activation_checkpointing_config)
            mcfg = getattr(model, "config", None)
            if hasattr(mcfg, "remat") and not mcfg.remat:
                mcfg.remat = True
                log_dist("activation checkpointing enabled from config",
                         ranks=[0])

        # -- sparse (row-sparse/CSR) embedding gradients --
        # reference auto-detects nn.Embedding modules (engine.py:180-185)
        # and exchanges their grads as CSR pairs; models here declare their
        # embedding leaves.  ZeRO shards the flat space and cannot carry a
        # row-sparse exchange (same incompatibility as the reference's
        # CSR-under-ZeRO).
        self._sparse_grad_paths = ()
        if self._config.sparse_gradients_enabled:
            if self._config.zero_optimization_stage != 0:
                raise ValueError(
                    f"sparse_gradients: true requires ZeRO stage 0, got "
                    f"stage={self._config.zero_optimization_stage} — the "
                    f"row-sparse (indices, values) exchange cannot ride a "
                    f"sharded flat parameter space (stages 1/2 shard the "
                    f"optimizer/gradient buffers, stage 3 additionally "
                    f"shards the parameters themselves; the reference has "
                    f"the same CSR-under-ZeRO limit).  Disable "
                    f"sparse_gradients or set zero_optimization.stage: 0.")
            if hasattr(model, "sparse_gradient_paths"):
                self._sparse_grad_paths = tuple(model.sparse_gradient_paths())
            log_dist(
                f"sparse_gradients: embedding leaves "
                f"{self._sparse_grad_paths or '(none declared)'} exchange as "
                f"row-sparse (indices, values) pairs over the data axis "
                f"(csr_allreduce inside a shard_map step); dense XLA "
                f"scatter-add remains the default when disabled — it is the "
                f"fast path on ICI; this trims wire bytes for huge "
                f"sparsely-touched embeddings over DCN", ranks=[0])

        # -- model / loss function --
        self.module = model
        if hasattr(model, "apply"):
            self._loss_fn = model.apply
        elif callable(model):
            self._loss_fn = model
        else:
            raise TypeError("model must expose .apply(params, batch, ...) or be callable")
        # a model may report scalars of its own beside the loss (an expert
        # layer's load, its auxiliary loss): ``apply_reporting(...) ->
        # (loss, {name: device scalar})``.  They leave the fused step as
        # outputs and are read at the print cadence alone
        self._loss_reports_fn = getattr(model, "apply_reporting", None)
        self._last_reports = {}

        # -- parameter init --
        rng_seed = int(self._config._param_dict.get("seed", 0))
        # PRNG implementation for the training rng stream (dropout, PLD).
        # "auto" picks the hardware-friendly rbg generator on TPU — threefry
        # costs ~30% of a BERT-large step once dropout is on, rbg is ~free —
        # and keeps jax's default (threefry) elsewhere.  Model-init keys are
        # unaffected (quality of init never rides on rbg).
        prng_impl = str(self._config._param_dict.get("prng_impl", "auto"))
        if prng_impl == "auto":
            prng_impl = ("rbg" if self.mesh.devices.flat[0].platform == "tpu"
                         else "threefry2x32")
        # typed key: the impl rides in the dtype, so split/fold_in downstream
        # (models, dropout) never mistake it for a default-impl raw key
        self._rng = jax.random.key(rng_seed, impl=prng_impl)
        # stochastic-rounding bit streams (reduced-precision offload
        # state) reuse the same impl choice: rbg bits are ~free on TPU
        self._prng_impl = prng_impl
        # model init always derives from threefry: same seed → same initial
        # params on every backend, independent of the training-stream impl
        init_rng = jax.random.PRNGKey(rng_seed)
        offload_cfg = bool(self._config.zero_config.cpu_offload)
        # plan mode (aot_plan=True): the capacity planner's engine.  The
        # whole parameter/optimizer state stays ABSTRACT — ShapeDtype
        # Structs with the real shardings — so "what fits now?" is
        # answered from avals before anything model-sized materializes
        # (at 1.8B params the concrete init alone costs minutes of host
        # RNG + ~22 GB of allocation the plan never reads).  Offload
        # plans keep the concrete path: their pinned-host buffers ARE
        # the quantity under measurement.
        self._aot_plan = bool(aot_plan)
        plan_abstract = (self._aot_plan and model_parameters is None
                         and not offload_cfg)
        if model_parameters is not None:
            params0 = model_parameters
        elif plan_abstract:
            assert hasattr(model, "init"), (
                "model has no .init(rng); pass model_parameters explicitly")
            params0 = jax.eval_shape(model.init, init_rng)
        else:
            assert hasattr(model, "init"), (
                "model has no .init(rng); pass model_parameters explicitly")
            params0 = None
            if offload_cfg:
                # ZeRO-Offload: init on the host CPU backend when one is
                # available so the fp32 init params never touch HBM — the
                # capacity ceiling is then set by the streamed step, not
                # by init (reference analog: ZeRO-Offload's "10x bigger
                # models" claim requires init to not be the limit either,
                # stage2.py:326-342).  Same seed → same params (init keys
                # are threefry on every backend).
                params0 = self._try_host_init(model, init_rng)
            if params0 is None:
                with self.mesh:
                    params0 = model.init(init_rng)
        if offload_cfg:
            # host leaves: the flatten consumes them leaf-wise on host;
            # putting them on device here would re-impose the init ceiling
            params0 = jax.tree_util.tree_map(np.asarray, params0)
        elif not plan_abstract:
            params0 = jax.tree_util.tree_map(jnp.asarray, params0)
        self._param_template = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, self.compute_dtype), params0)

        # TP sharding rules for module params
        if hasattr(model, "partition_specs"):
            self._param_specs = model.partition_specs(self.mesh)
        else:
            self._param_specs = jax.tree_util.tree_map(lambda _: P(), params0)

        # -- ZeRO flat parameter space (see zero/ package for the policy) --
        from .zero.coordinator import FlatParamCoordinator

        self.zero_stage = self._config.zero_optimization_stage
        zc = self._config.zero_config
        # the offload keys' half of the layout (uniform-chunk alignment,
        # the master's storage dtype, the host-buffer families to cap)
        from .zero.offload import host_state_dtypes, layout_args

        self._state_reduced = bool(
            getattr(zc, "offload_state_reduced", False))
        # -- bucketed gradient-collective overlap (overlap_comm, round
        # 14): decide BEFORE the coordinator builds, because the
        # overlapped exchange requires the shard-major sub-partition
        # layout (zero/buckets.py) the coordinator owns.  "auto"
        # engages whenever the bucketed exchange is supported; an
        # explicit true raises on any unmet requirement; false keeps
        # the GSPMD fused exchange (the serialized control).
        self._comm_overlap, self._comm_overlap_unsupported = \
            self._resolve_comm_overlap(zc, optimizer)
        bucket_plan = None
        if self._comm_overlap:
            from .zero.buckets import BucketPlan

            bucket_plan = BucketPlan(
                [int(np.prod(x.shape))
                 for x in jax.tree_util.tree_leaves(params0)],
                dp=self.dp_world_size,
                reduce_bucket_size=zc.reduce_bucket_size,
                allgather_bucket_size=zc.allgather_bucket_size)
        self.flat = FlatParamCoordinator(
            mesh=self.mesh, params_template=params0, stage=self.zero_stage,
            dp_size=self.dp_world_size,
            cpu_offload=zc.cpu_offload,
            group_bytes=(zc.offload_group_mb << 20
                         if getattr(zc, "offload_group_mb_explicit", False)
                         else None),
            bucket_plan=bucket_plan, **layout_args(zc))
        self.segments = self.flat.segments
        if self._comm_overlap:
            what = ("JIT parameter gathers + bucketed gradient exchange"
                    if self.zero_stage >= 3 else
                    "bucketed gradient exchange")
            log_dist(
                f"ZeRO-{self.zero_stage} overlap_comm: {what} — "
                f"{bucket_plan.n_buckets} reduce bucket(s) "
                f"(reduce_bucket_size={zc.reduce_bucket_size}), "
                f"{len(bucket_plan.ag_groups)} all-gather group(s) "
                f"(allgather_bucket_size={zc.allgather_bucket_size}), "
                f"shard-major sub-partition layout over dp="
                f"{self.dp_world_size}", ranks=[0])

        # master weights (flat fp32, sharded per stage)
        if plan_abstract:
            # the coordinator's layout is fully determined by shapes:
            # the abstract master is (flat_rows, LANES) fp32 under the
            # real device sharding — layout-exact, zero bytes
            master0 = jax.ShapeDtypeStruct(
                self.flat.flat_shape, jnp.float32,
                sharding=self.flat.master_device_sharding)
        else:
            master0 = self.flat.flatten_to_master(params0)
        if self._config.zero_config.cpu_offload:
            # free the fp32 init params BEFORE later init work dispatches:
            # with state host-offloaded, the async param cast otherwise
            # executes while these ~4 bytes/param still occupy HBM — at
            # ~1B params the overlap alone exhausts the chip (measured:
            # the streamed cast ResourceExhausted at 1.0B until this del).
            # Only effective for engine-initialized params: a caller who
            # PASSES model_parameters as live jax arrays keeps their own
            # references, and that HBM stays pinned as long as they do.
            del params0
            model_parameters = None

        # -- optimizer (reference _configure_optimizer engine.py:544-712) --
        self.client_optimizer = optimizer
        self.optimizer = self._configure_basic_optimizer(optimizer)
        self._opt_shardings = self._make_opt_shardings()
        # offload mode: 'injit' (TPU — programs stream host<->device
        # themselves) or 'eager' (state parked in pinned host between steps)
        self._offload = self.flat.cpu_offload
        self._offload_eager = self._offload and not self.flat.injit_placement
        if self._state_reduced:
            # loud, not silent: the flag exists to halve the wire bytes
            # of the STREAMED update — paths that cannot stream (eager
            # offload parks full buffers; non-Adam optimizers take the
            # one-shot update) would run fp32 math on reduced storage
            # or silently keep fp32 wire traffic
            if self._offload_eager:
                raise ValueError(
                    "offload_state_dtype with reduced dtypes requires "
                    "in-jit host placement (TPU backend, or "
                    "DS_OFFLOAD_FORCE_INJIT=1 for CPU tests); this "
                    "backend only supports eager offload mode")
            if getattr(self.optimizer, "name", "") != "adam":
                raise ValueError(
                    "offload_state_dtype with reduced dtypes requires "
                    "the flat Adam optimizer (the chunk-streamed update "
                    "the compression rides)")
        if self._offload and self.flat.memory_spaces:
            self._opt_shardings_device = jax.tree_util.tree_map(
                lambda s: s.with_memory_kind("device"), self._opt_shardings)
        elif self._offload:
            # single-memory-space backends (CPU — eager offload, or the
            # forced in-jit test mode): the "device" copy of the
            # shardings is the default-space variant
            self._opt_shardings_device = jax.tree_util.tree_map(
                lambda s: NamedSharding(s.mesh, s.spec), self._opt_shardings)
        else:
            self._opt_shardings_device = self._opt_shardings
        if (self.flat.host_group_bounds is not None
                and getattr(self.optimizer, "name", "") != "adam"):
            raise ValueError(
                "cpu_offload with state this large (row-grouped host "
                "buffers) requires an Adam-family flat optimizer — "
                "reference parity: ZeRO-Offload pairs with [CPU]Adam "
                "(stage2.py:326, zero/utils.py:26)")
        with self.mesh:
            if self._offload and getattr(self.optimizer, "name", "") in (
                    "adam", "cpu_adam", "lamb"):
                # offload state: host-side zero init (every flat optimizer
                # here is zeros_like + a step scalar — asserted by
                # test_zero_offload); running init_state on device would
                # materialize full fp32 state in HBM just to write zeros
                opt_shape = jax.eval_shape(
                    self.optimizer.init_state,
                    jax.ShapeDtypeStruct(self.segments.shape, jnp.float32))
                bounds = (self.flat.host_group_bounds
                          or ((0, self.segments.rows),))
                # reduced host state: flat leaves store in their
                # configured dtype (exp_avg -> momentum, exp_avg_sq ->
                # variance); scalars and the fp32 default are untouched
                sd_by_name = (host_state_dtypes(zc)
                              if self._state_reduced else {})

                def _mk(leaf, dtype):
                    if leaf.shape == self.segments.shape:
                        grps = tuple(
                            self.flat.home_host(np.zeros((rc, LANES),
                                                         np.dtype(dtype)))
                            for _, rc in bounds)
                        return (grps if self.flat.host_group_bounds
                                is not None else grps[0])
                    return jnp.zeros(leaf.shape, leaf.dtype)

                flat_sh, opt_def0 = jax.tree_util.tree_flatten_with_path(
                    opt_shape)
                opt0 = jax.tree_util.tree_unflatten(opt_def0, [
                    _mk(leaf, sd_by_name.get(
                        tree_path_key(path).lstrip("."), leaf.dtype))
                    for path, leaf in flat_sh])
            elif self.flat.host_group_bounds is not None:
                raise ValueError(
                    "cpu_offload with row-grouped host state requires a "
                    "zeros-init flat optimizer (adam/lamb family), got "
                    f"{getattr(self.optimizer, 'name', type(self.optimizer))}")
            elif plan_abstract:
                # abstract optimizer state with the real shardings: the
                # step program lowers from these avals directly
                opt0 = jax.tree_util.tree_map(
                    lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                      sharding=s),
                    jax.eval_shape(self.optimizer.init_state, master0),
                    self._opt_shardings_device)
            else:
                master0_dev = (jax.device_put(
                    master0, self.flat.master_device_sharding)
                    if self._offload else master0)
                opt0 = jax.jit(self.optimizer.init_state,
                               out_shardings=self._opt_shardings_device)(
                    master0_dev)
                if self._offload:
                    opt0 = jax.device_put(opt0, self._opt_shardings)
                    del master0_dev

        scale0 = DynamicScaleState.create(
            init_scale=(self._config.initial_dynamic_scale
                        if self.dynamic_loss_scale_enabled else self.static_loss_scale),
            delayed_shift=(self._config.dynamic_loss_scale_args or {}).get(
                "delayed_shift", 1))

        # host-resident flat gradients (ZeRO-Offload's gradient leg,
        # reference stage2.py:622-668): only meaningful under in-jit
        # streaming; the buffer is donated through every fused step
        offload_grads_requested = bool(
            getattr(self._config.zero_config, "offload_gradients", False))
        self._offload_grads = (offload_grads_requested and self._offload
                               and not self._offload_eager)
        if offload_grads_requested and not self._offload_grads:
            # loud, not silent: the flag exists to eliminate the
            # 4 bytes/param device gradient buffer — dropping it quietly
            # would let the job OOM at exactly the scale the flag was set
            # to reach
            raise ValueError(
                "offload_gradients requires in-jit host placement (TPU "
                "backend); this backend only supports eager offload mode")
        if self._offload_grads:
            if self._sparse_grad_paths:
                raise ValueError(
                    "offload_gradients does not compose with "
                    "sparse_gradients (the row-sparse shard_map exchange "
                    "has no host-streamed form)")
            if getattr(self.optimizer, "name", "") != "adam":
                raise ValueError(
                    "offload_gradients requires the flat Adam optimizer "
                    "(the chunk-streamed update)")
            if self.gradient_accumulation_steps() > 1:
                raise ValueError(
                    "offload_gradients does not yet support "
                    "gradient_accumulation_steps > 1 (the host gradient "
                    "buffer is written once per fused step)")
        hostgrad0 = (self.flat.alloc_host_grads()
                     if self._offload_grads else None)

        # persistent error-feedback residuals (reduced-precision offload
        # state): one pinned-host buffer per reduced
        # state buffer, grouped like the master, zero-init (the init
        # downcast error is absorbed within the first few steps)
        qres0 = None
        if (self._state_reduced
                and zc.offload_state_dtype["error_feedback"]):
            res_bounds = (self.flat.host_group_bounds
                          or ((0, self.segments.rows),))

            def _zeros_grouped(dtype):
                grps = tuple(
                    self.flat.home_host(np.zeros((rc, LANES),
                                                 np.dtype(dtype)))
                    for _, rc in res_bounds)
                return (grps if self.flat.host_group_bounds is not None
                        else grps[0])

            qres0 = {name: _zeros_grouped(dtype)
                     for name, dtype in host_state_dtypes(zc).items()
                     if dtype != jnp.float32}

        self.state = {
            "master": master0,
            "opt": opt0,
            "hostgrad": hostgrad0,
            "qres": qres0,
            **self._step_scalars(
                scale0, jnp.asarray(0, jnp.int32),
                # device-resident step counter: the fused train step derives
                # its dropout/rng stream from it on-device, so no per-step
                # host scalar transfer is needed
                jnp.asarray(0, jnp.uint32)),
        }

        # cached module-dtype params (stage<=2 keeps them resident;
        # stage 3 materializes them inside fwd_bwd from the sharded master)
        self._module_params = None
        self._train_step_compressed_fn = None

        # -- schedules / aux --
        self.lr_scheduler = self._configure_lr_scheduler(lr_scheduler)
        self.progressive_layer_drop = (ProgressiveLayerDrop(
            theta=self._config.pld_params["theta"],
            gamma=self._config.pld_params["gamma"])
            if self._config.pld_enabled else None)

        from ..profiling.flops_profiler import FlopsProfiler
        from ..utils.monitor import TrainingMonitor

        self.flops_profiler = (FlopsProfiler(self)
                               if self._config.flops_profiler_config.enabled
                               else None)
        self.monitor = TrainingMonitor(
            self._config.tensorboard_enabled,
            self._config.tensorboard_output_path,
            self._config.tensorboard_job_name,
            rank=jax.process_index())
        self.timers = SynchronizedWallClockTimer()
        self.tput_timer = ThroughputTimer(
            batch_size=self.train_micro_batch_size_per_gpu() * self.dp_world_size,
            num_workers=1, steps_per_output=self.steps_per_print())

        # -- telemetry (deepspeed_tpu/telemetry): the monitor becomes a
        # consumer of the event stream — scalars flow through
        # telemetry.step_metrics, which feeds TB/JSONL unchanged.  Every
        # telemetry call below is host-only Python on scalars fetched by
        # the EXISTING batched steps_per_print transfer: zero new syncs.
        from ..telemetry.manager import TelemetryManager

        self.telemetry_config = self._config.telemetry_config
        self.telemetry = TelemetryManager(self.telemetry_config,
                                          rank=jax.process_index(),
                                          monitor=self.monitor)
        if self.telemetry.enabled:
            # compile events/spans + cache hit/miss counters off
            # jax.monitoring listeners: host-only, nothing on the step
            # path (compiles happen at trace time), zero new syncs
            from .compilation import install_compile_telemetry

            install_compile_telemetry(self.telemetry)

        # -- memory + communication observability (deepspeed_tpu/
        # profiling): the compiled-program ledgers wrap every jit entry
        # point built in _build_step_functions (memory_analysis AND the
        # optimized HLO's collectives recorded at compile time); HBM
        # watermarks, the host-buffer registry, and the per-rank
        # step-latency/skew exchange are sampled ONLY at the
        # steps_per_print cadence — zero new per-step syncs
        from ..profiling.comm import CommLedger
        from ..profiling.memory import MemoryLedger

        self.profiling_config = self._config.profiling_config
        self.comm_ledger = CommLedger(
            enabled=self.profiling_config.comm_ledger_enabled(
                self.telemetry.enabled),
            telemetry=self.telemetry,
            mesh_axes=mesh_axis_sizes(self.mesh))
        # the overlap analyzer (profiling/overlap) rides the same one
        # compile-time HLO walk: the context resolves lazily because
        # the declared host-state stream and donation specs are only
        # final after _build_step_functions
        self.comm_ledger.overlap_context_fn = self.program_verify_context
        # the comm ledger and the program dumper both ride the memory
        # ledger's AOT hook, so either being on forces the shared hook
        # on even with the memory ledger off (memory events stay gated
        # on the memory ledger's own knob).  An explicit
        # program_dump=true with both ledgers off must still dump —
        # record() is the only dump site, so the hook must be live
        mem_on = (self.profiling_config.memory_ledger_enabled(
            self.telemetry.enabled) or self._aot_plan)
        dump_on = (self.profiling_config.program_dump_enabled(
            self.comm_ledger.enabled)
            and bool(getattr(self.telemetry, "run_dir", None)))
        self.memory_ledger = MemoryLedger(
            enabled=mem_on or self.comm_ledger.enabled or dump_on,
            telemetry=self.telemetry,
            comm_ledger=(self.comm_ledger if self.comm_ledger.enabled
                         else None),
            record_memory=mem_on)
        self._memory_watermarks = (
            self.profiling_config.memory_watermarks_enabled(
                self.telemetry.enabled))
        # per-program verification artifacts (profiling/verify): the
        # ledger's one compile-time recording also lands HLO + sidecar
        # under <run_dir>/programs/ for `dslint --programs` — the
        # DSP6xx program verifier's offline input.  Rank 0 only;
        # donation/mesh context resolves lazily (specs are final only
        # after _build_step_functions, programs record on first
        # dispatch)
        if dump_on:
            from ..profiling.verify import ProgramDumper

            self.memory_ledger.dumper = ProgramDumper(
                self.telemetry.run_dir, rank=jax.process_index(),
                context_fn=self.program_verify_context,
                donation_fn=lambda name: (
                    getattr(self, "_donation_specs", {}).get(name)
                    or None))
        self.telemetry.emit(
            TEL.EVENT_RUN_START, step=0, world_size=self.world_size,
            dp=self.dp_world_size,
            precision=("fp16" if self._config.fp16_enabled else
                       "bf16" if self._config.bf16_enabled else "fp32"),
            zero_stage=self.zero_stage)

        self.global_steps = 0
        self.micro_steps = 0
        self.global_samples = 0
        self._losses = []
        self._acc_grads = None
        self._overflow = False

        # -- data pipeline (reference deepspeed_io engine.py:719-760) --
        self.training_dataloader = None
        if training_data is not None:
            self.training_dataloader = self.deepspeed_io(training_data,
                                                         collate_fn=collate_fn)
        self.collate_fn = collate_fn

        if not dont_build_steps:
            self._build_step_functions()
            if not self._aot_plan:
                with self.mesh:
                    self._refresh_module_params()

        # -- checkpoint subsystem (deepspeed_tpu/checkpoint) --
        self.checkpoint_config = self._config.checkpoint_config
        self._ckpt_manager = CheckpointManager(self.checkpoint_config)
        # lifecycle events (queue depth, commit latency/bytes/retries)
        # ride the manager's own save/commit paths, including the
        # background writer threads (EventLog/registry are thread-safe)
        self._ckpt_manager.telemetry = self.telemetry
        self._last_ckpt_dir = None
        if self.checkpoint_config.save_on_preemption:
            self._ckpt_manager.install_preemption_handler(
                self._preemption_save)

        # -- resilience runtime guards (deepspeed_tpu/resilience) --
        rcfg = self.resilience_config
        self._guard = None
        self._rollback_mgr = None
        self._watchdog = None
        self._step_latencies = None
        if rcfg.enabled:
            from ..resilience.guard import AnomalyGuard
            from ..resilience.rollback import RollbackManager

            scale_args = self._config.dynamic_loss_scale_args or {}
            self._guard = AnomalyGuard(
                policy=rcfg.policy, spike_window=rcfg.spike_window,
                spike_zscore=rcfg.spike_zscore,
                divergence_patience=rcfg.divergence_patience,
                floor_scale_patience=rcfg.floor_scale_patience,
                min_scale=float(scale_args.get("min_scale", 1.0)),
                fp16=self._config.fp16_enabled,
                event_sink=self._telemetry_anomaly)
            self._rollback_mgr = RollbackManager(
                self, max_rollbacks=rcfg.max_rollbacks,
                cooldown_steps=rcfg.rollback_cooldown_steps,
                checkpoint_dir=rcfg.checkpoint_dir)
            if rcfg.hang_timeout_secs > 0:
                from ..profiling.step_profiler import StepLatencyRing
                from ..resilience.watchdog import StepWatchdog

                self._step_latencies = StepLatencyRing()
                self._watchdog = StepWatchdog(
                    rcfg.hang_timeout_secs,
                    latency_ring=self._step_latencies,
                    describe=lambda: (
                        f"global_step={self.global_steps} "
                        f"micro_steps={self.micro_steps}"),
                    on_fire=self._telemetry_watchdog_fire).start()
            log_dist(f"resilience enabled: {rcfg}", ranks=[0])

        # -- fleet integrity plane (deepspeed_tpu/resilience/integrity):
        # per-rank state fingerprints + majority vote, fleet heartbeats
        # + hang quorum.  The exchange medium is the telemetry run dir
        # (the PR-8 latency-rank*.json atomic-file pattern), so like the
        # skew export it needs telemetry on; the fingerprint scalar
        # rides the EXISTING batched steps_per_print fetch — zero new
        # per-step host syncs (device_get-counting test covers it)
        self._integrity = None
        self._fleet_heartbeat = None
        self._fingerprint_jit = None
        if rcfg.enabled and rcfg.integrity:
            if not (self.telemetry.enabled and self.telemetry.run_dir):
                logger.warning(
                    "resilience.integrity needs telemetry enabled with a "
                    "run_dir (the fingerprint/heartbeat exchange medium); "
                    "integrity plane disabled")
            else:
                from ..launcher.constants import (ENV_NUM_PROCESSES,
                                                  ENV_PROCESS_ID)
                from ..resilience.integrity import (FleetHeartbeat,
                                                    IntegrityPlane)

                # fleet identity: the launcher's env contract when
                # spawned under it (each process one fleet rank), else
                # the jax multi-controller identity
                fleet_rank = int(os.environ.get(ENV_PROCESS_ID, "")
                                 or jax.process_index())
                fleet_size = int(os.environ.get(ENV_NUM_PROCESSES, "")
                                 or jax.process_count())
                if fleet_size < 2:
                    # min_quorum is always >= 2: a single process can
                    # never reach a verdict, so don't pay a full-state
                    # jitted checksum + run-dir I/O every print cadence
                    # for an eternally-pending vote
                    logger.warning(
                        "resilience.integrity: fingerprint consensus "
                        "needs a fleet of >= 2 ranks (single process "
                        "can never reach a voting quorum); integrity "
                        "plane not armed")
                elif jax.process_count() > 1:
                    # the consensus model needs each process's checksum
                    # computed over process-LOCAL replica state (the
                    # launcher's full-replica fleet contract, one jax
                    # world per process).  Under a multi-controller
                    # rendezvous the state arrays are jointly sharded
                    # and the in-jit checksum compiles to a GLOBAL
                    # cross-process reduction: every process publishes
                    # the identical value, the vote can never name a
                    # suspect, and a corrupted shard reads as a
                    # unanimous "ok" — worse than no detection at all
                    logger.warning(
                        "resilience.integrity: fingerprint consensus "
                        "disabled under a jax multi-controller "
                        "rendezvous (the in-jit checksum over jointly "
                        "sharded state is a global reduction — every "
                        "process publishes the same value and the vote "
                        "is blind); fleet heartbeat still armed")
                elif self._config.zero_config.cpu_offload:
                    # the offloaded (master, opt) state is host-resident
                    # BECAUSE it does not fit on device: checksumming it
                    # in-jit would re-upload the whole state at every
                    # print cadence (or OOM and silently disable).  A
                    # chunked host-side checksum is future work; the
                    # heartbeat/hang-quorum half stays armed
                    logger.warning(
                        "resilience.integrity: fingerprint consensus "
                        "disabled under ZeRO-Offload (in-jit checksum "
                        "would re-transfer the host-resident state each "
                        "print cadence); fleet heartbeat still armed")
                else:
                    self._integrity = IntegrityPlane(
                        self.telemetry.run_dir, rank=fleet_rank,
                        fleet_size=fleet_size,
                        window=rcfg.integrity_window,
                        action=rcfg.integrity_action)
                if rcfg.integrity_peer_timeout_secs > 0:
                    if fleet_size >= 3:
                        self._fleet_heartbeat = FleetHeartbeat(
                            self.telemetry.run_dir, rank=fleet_rank,
                            fleet_size=fleet_size,
                            peer_timeout_secs=(
                                rcfg.integrity_peer_timeout_secs),
                            action=rcfg.integrity_action,
                            on_fire=self._telemetry_integrity_hang,
                        ).start()
                    elif fleet_size == 2:
                        # with 2 ranks a strict majority at the head
                        # means BOTH are at the head (no lagging
                        # suspect), and a lone leader is no majority:
                        # the quorum can mathematically never convict —
                        # don't pay a monitor thread + per-step beats
                        # for an inert mechanism
                        logger.warning(
                            "resilience.integrity: hang quorum needs a "
                            "fleet of >= 3 ranks (2 ranks can never "
                            "reach a convicting majority); fleet "
                            "heartbeat not armed — each rank's local "
                            "watchdog remains the hang authority")
                launcher_dir = os.environ.get("DS_TELEMETRY_DIR")
                if launcher_dir and (os.path.abspath(launcher_dir)
                                     != os.path.abspath(
                                         self.telemetry.run_dir)):
                    # the launcher consumes verdicts / clears fleet
                    # state from ITS --telemetry-dir; an exchange
                    # happening elsewhere makes every eviction blind
                    # (suspect never blocklisted) and leaves stale
                    # fleet state to convict the rolled-back fleet
                    logger.warning(
                        "resilience.integrity: telemetry.run_dir "
                        f"({self.telemetry.run_dir}) differs from the "
                        f"launcher's --telemetry-dir ({launcher_dir}); "
                        "the launcher consumes integrity verdicts and "
                        "clears fleet state from its own dir, so "
                        "eviction recovery will NOT see this run's "
                        "verdicts — drop telemetry.run_dir from the "
                        "config or point both at the same directory")
                armed = [h for h, on in (
                    ("fingerprint consensus", self._integrity is not None),
                    ("hang quorum", self._fleet_heartbeat is not None),
                ) if on]
                if armed:
                    log_dist(
                        f"fleet integrity plane armed "
                        f"({', '.join(armed)}): rank {fleet_rank}/"
                        f"{fleet_size}, window {rcfg.integrity_window}, "
                        f"action {rcfg.integrity_action}, peer timeout "
                        f"{rcfg.integrity_peer_timeout_secs:g}s",
                        ranks=[0])
        from ..profiling.step_profiler import StepLatencyRing

        if self._step_latencies is None:
            # no watchdog armed: the ring self-tracks beats
            # (watchdog.beat feeds it otherwise — see _step_beat).
            # Always on since round 13 (O(1) host work per step): the
            # telemetry skew export AND the attribution receipt's
            # measured side both read it, and bench/dryrun engines run
            # with telemetry off
            self._step_latencies = StepLatencyRing()
        # host-side driver seconds per step (batch fetch through the
        # async dispatch enqueue; the blocking scalar fetch is device
        # time, not driver), recorded by a perf_counter bracket the
        # train path already pays — the attribution driver phase
        self._driver_latencies = StepLatencyRing()

        if self._config.dump_state:
            self._config.print("DeepSpeedEngine configuration")

    # ------------------------------------------------------------------
    # configuration accessors (reference engine.py:217-398)
    # ------------------------------------------------------------------
    def train_batch_size(self):
        return self._config.train_batch_size

    def train_micro_batch_size_per_gpu(self):
        return self._config.train_micro_batch_size_per_gpu

    def gradient_accumulation_steps(self):
        return self._config.gradient_accumulation_steps

    def steps_per_print(self):
        return self._config.steps_per_print

    def zero_optimization(self):
        return self._config.zero_enabled

    def zero_optimization_stage(self):
        return self._config.zero_optimization_stage

    def zero_cpu_offload(self):
        return self._config.zero_config.cpu_offload

    def host_state_dtype(self):
        """Storage dtype of the offloaded host state: one canonical name
        when master/momentum/variance agree, else "mixed" (bench rows and
        telemetry quote this next to host_state_bytes_per_step)."""
        sd = self._config.zero_config.offload_state_dtype
        names = {sd["master"], sd["momentum"], sd["variance"]}
        return sd["master"] if len(names) == 1 else "mixed"

    def host_state_bytes_per_step(self):
        """Wire bytes the streamed update moves per step for the host
        optimizer state (both directions; gradients separate).  None
        when offload is off."""
        return getattr(self, "_host_state_bytes_per_step", None)

    @property
    def _rr_disabled_logged(self):
        return self._offload_stream.rr_disabled_logged

    def host_stream_schedule(self):
        """Declared issue schedule of the streamed offload update
        (``{overlap, prefetch_depth, chunks, groups, form, ...}``) —
        the structure the overlap analyzer prices the exposed-wire
        fraction from.  None when the update does not stream."""
        return getattr(self, "_host_stream_schedule", None)

    def collective_schedule(self):
        """Declared issue schedule of the ZeRO-2 data-parallel gradient
        exchange (``{overlap, rs_buckets, ag_buckets, ...}``) — what
        the overlap analyzer prices the exposed collective wire from.
        None when the bucketed exchange is unsupported on this
        config/mesh (no claim either way)."""
        return getattr(self, "_collective_schedule", None)

    def comm_overlap_enabled(self):
        """True when the bucketed overlapped gradient exchange
        (``zero_optimization.overlap_comm``) is active."""
        return bool(getattr(self, "_comm_overlap", False))

    def fp16_enabled(self):
        return self._config.fp16_enabled

    def bfloat16_enabled(self):
        return self._config.bf16_enabled

    def dynamic_loss_scale(self):
        return self.dynamic_loss_scale_enabled

    def gradient_clipping(self):
        return self._config.gradient_clipping

    def wall_clock_breakdown(self):
        return self._config.wall_clock_breakdown

    def sparse_gradients_enabled(self):
        return self._config.sparse_gradients_enabled

    def sparse_gradient_paths(self):
        """Embedding leaves declared row-sparse by the model (for tooling
        and custom DCN exchanges via ``runtime.csr_tensor.csr_allreduce``;
        the in-engine reduction on ICI is dense scatter-add either way)."""
        return self._sparse_grad_paths

    def progressive_layer_drop_enabled(self):
        return self._config.pld_enabled

    @property
    def loss_scale(self):
        return float(jax.device_get(self.state["scale"].cur_scale))

    @property
    def skipped_steps(self):
        return int(jax.device_get(self.state["skipped"]))

    def get_lr(self):
        return [g["lr"] for g in self.optimizer.param_groups]

    def get_params(self):
        """Current parameters as an (unsharded view) pytree in compute dtype."""
        if self._module_params is not None:
            return self._module_params
        with self.mesh:
            return self._cast_params_fn(self._device_master())

    def get_master_params(self):
        return self.state["master"]

    # ------------------------------------------------------------------
    # telemetry plumbing (deepspeed_tpu/telemetry)
    # ------------------------------------------------------------------
    def _telemetry_anomaly(self, step, kind, detail):
        """AnomalyGuard event sink: every classified anomaly lands in the
        structured event stream (host scalars only — the guard already
        runs on the one batched per-step fetch)."""
        self.telemetry.emit(
            TEL.EVENT_ANOMALY, step=step, kind=kind, detail=detail,
            consecutive=(self._guard.consecutive_anomalies
                         if self._guard is not None else 0))
        self.telemetry.counter("resilience/anomalies").inc()

    def _telemetry_watchdog_fire(self, stalled_secs):
        """Watchdog fire hook: the process dies via ``os._exit`` next, so
        the tail events must be flushed HERE — atexit never runs."""
        self.telemetry.emit(
            TEL.EVENT_WATCHDOG_HANG, step=self.global_steps,
            stalled_secs=float(stalled_secs),
            timeout_secs=float(self.resilience_config.hang_timeout_secs))
        self.telemetry.flush(reason="watchdog_hang")

    def _step_beat(self):
        """One completed step: feeds the step-latency ring (through the
        watchdog's heartbeat when it is armed — it owns the interval
        tracking then).  O(1) host work, no device access."""
        if self._watchdog is not None:
            self._watchdog.beat()
        elif self._step_latencies is not None:
            self._step_latencies.beat()

    def _step_beat_pause(self):
        """Forget the last beat across a known-long gap (rollback
        restore, synchronous final save) so it neither trips the
        watchdog nor records as a step latency."""
        if self._watchdog is not None:
            self._watchdog.pause()
        if self._step_latencies is not None:
            self._step_latencies.pause()
        if self._fleet_heartbeat is not None:
            self._fleet_heartbeat.pause()

    # ------------------------------------------------------------------
    # fleet integrity plane (deepspeed_tpu/resilience/integrity)
    # ------------------------------------------------------------------
    def _integrity_step_enter(self):
        """Entering one optimizer step: publish the fleet heartbeat
        (throttled atomic file write — O(1) host work, no device
        access).  Placed AFTER the batch fetch so a wedged input
        pipeline never publishes the step it failed to enter: the lag
        is exactly what the hang quorum discriminates on."""
        if self._fleet_heartbeat is not None:
            self._fleet_heartbeat.beat(self.global_steps + 1)

    def _telemetry_integrity_hang(self, verdict):
        """FleetHeartbeat fire hook: the process exits via ``os._exit``
        next (the main thread may be wedged inside a collective), so
        the verdict event must be emitted AND flushed here."""
        self.telemetry.emit(
            TEL.EVENT_INTEGRITY, step=self.global_steps,
            verdict="outlier", kind="hang_quorum",
            suspects=[verdict["suspect"]],
            stalled_secs=float(verdict["stalled_secs"]),
            suspect_step=verdict["suspect_step"],
            head_step=verdict["head_step"], voters=verdict["leaders"])
        self.telemetry.counter("integrity/violations").inc()
        self.telemetry.flush(reason="integrity_hang_quorum")

    def _integrity_fingerprint_device(self):
        """Dispatch the in-jit state checksum; returns the uint32
        device scalar (or None with the plane off / a backend that
        cannot run it).  The value is NOT fetched here — it joins the
        one existing batched ``steps_per_print`` ``device_get`` so the
        fingerprint adds zero host syncs.

        The checksum is a position-weighted sum of the raw bits of
        every (master, optimizer-state) leaf in uint32 wraparound
        arithmetic: integer math, so replicas that are bit-identical
        produce identical fingerprints on any backend, and a single
        flipped bit anywhere changes the sum."""
        if self._integrity is None:
            return None
        if self._fingerprint_jit is False:     # prior failure: disabled
            return None
        if self._fingerprint_jit is None:
            from jax import lax

            _BIT_UINTS = {1: jnp.uint8, 2: jnp.uint16}

            def _leaf_bits(leaf):
                x = jnp.asarray(leaf)
                if x.dtype == jnp.bool_:
                    x = x.astype(jnp.uint8)
                if x.dtype.itemsize >= 4:
                    if x.dtype != jnp.uint32:
                        # 8-byte dtypes (x64 mode) bitcast to a trailing
                        # pair of uint32 words — never truncated
                        x = lax.bitcast_convert_type(x, jnp.uint32)
                    return x.reshape(-1)
                if not jnp.issubdtype(x.dtype, jnp.unsignedinteger):
                    x = lax.bitcast_convert_type(
                        x, _BIT_UINTS[x.dtype.itemsize])
                return x.reshape(-1).astype(jnp.uint32)

            def _fingerprint(master, opt):
                acc = jnp.zeros((), jnp.uint32)
                for leaf in jax.tree_util.tree_leaves((master, opt)):
                    bits = _leaf_bits(leaf)
                    # position weights forced ODD (|1): an odd weight is
                    # a unit mod 2^32, so flipping ANY single bit b
                    # moves the sum by 2^b * w != 0 — an even weight
                    # would make MSB flips at that position invisible.
                    # Distinct-per-position via the Knuth multiplier:
                    # catches element swaps a plain sum would miss
                    w = (jnp.arange(bits.size, dtype=jnp.uint32)
                         * jnp.uint32(2654435761)) | jnp.uint32(1)
                    acc = acc + jnp.sum(bits * w, dtype=jnp.uint32)
                return acc

            self._fingerprint_jit = jax.jit(_fingerprint)
        try:
            with self.mesh:
                return self._fingerprint_jit(self.state["master"],
                                             self.state["opt"])
        except Exception as e:  # noqa: BLE001 — observability only
            logger.error(
                "integrity fingerprint program failed (%s); disabling "
                "the fingerprint exchange on this rank", e)
            self._fingerprint_jit = False
            return None

    def _sample_integrity(self, fingerprint):
        """Publish this rank's fingerprint, read the fleet, vote, and
        escalate per ``resilience.integrity_action``.  Called only from
        the steps_per_print cadence block with the scalar the batched
        fetch already transferred — host arithmetic + run-dir file I/O
        only, ZERO added per-step syncs (dslint DSH205 pins the
        publish/read APIs to this cadence statically)."""
        if self._integrity is None or fingerprint is None:
            return
        from ..resilience import integrity as integ

        verdict = self._integrity.note_fingerprint(self.global_steps,
                                                   int(fingerprint))
        self.telemetry.gauge("integrity/fleet_voters").set(
            float(verdict["voters"]))
        self.telemetry.emit(
            TEL.EVENT_INTEGRITY, step=self.global_steps,
            verdict=verdict["verdict"], kind="fingerprint",
            suspects=verdict["suspects"],
            fingerprint=self._integrity.history.get(self.global_steps),
            majority_fingerprint=verdict["fingerprint"],
            voted_step=verdict["step"], voters=verdict["voters"])
        if verdict["verdict"] in (integ.VERDICT_OK, integ.VERDICT_PENDING):
            return
        self.telemetry.counter("integrity/violations").inc()
        if self._integrity.action != "evict":
            logger.error(
                "integrity verdict %s at step %s (suspects %s) — "
                "integrity_action=warn, continuing", verdict["verdict"],
                verdict["step"], verdict["suspects"])
            return
        from ..resilience.constants import (FleetIntegrityError,
                                            TrainingDivergedError)

        if self._watchdog is not None:
            # the eviction/poison teardown (flush, verdict write, the
            # script's exit) must never be preempted by the watchdog's
            # respawnable os._exit
            self._watchdog.stop()
        if self._fleet_heartbeat is not None:
            self._fleet_heartbeat.stop()
        if verdict["verdict"] == integ.VERDICT_NO_MAJORITY:
            msg = (f"fleet integrity: NO MAJORITY among "
                   f"{verdict['voters']} rank(s) at step "
                   f"{verdict['step']} — nobody can say which replica "
                   f"is right; poisoning the run")
            self.telemetry.emit(TEL.EVENT_ABORT, step=self.global_steps,
                                reason=msg)
            self.telemetry.flush(reason="integrity_no_majority")
            raise TrainingDivergedError(msg)
        suspect = verdict["suspects"][0]
        detail = (f"state fingerprint of rank(s) {verdict['suspects']} "
                  f"disagrees with the majority of {verdict['voters']} "
                  f"voter(s) at step {verdict['step']} "
                  f"(majority {verdict['fingerprint']})")
        self._integrity.record_eviction_verdict(
            integ.KIND_SDC, suspect, detail, step=verdict["step"])
        self.telemetry.flush(reason="integrity_evict")
        raise FleetIntegrityError(
            f"fleet integrity: {detail}; exiting for eviction resize",
            suspect=suspect, kind=integ.KIND_SDC)

    # ------------------------------------------------------------------
    # communication observability (deepspeed_tpu/profiling/comm)
    # ------------------------------------------------------------------
    def _active_step_program(self):
        """Name of the fused step program the NEXT dispatch runs: a
        1-bit Adam engine switches to its compressed program at
        freeze_step, and the comm receipt must follow (quoting warmup
        wire bytes forever would mask exactly the reduction 1-bit
        compression exists to deliver)."""
        if (self._train_step_compressed_fn is not None
                and self.global_steps >= self.optimizer.freeze_step):
            return "train_step_compressed"
        return "train_step"

    def comm_wire_bytes_per_step(self):
        """Predicted collective wire bytes one optimizer step moves
        (from the comm ledger's compile-time HLO walk); None until the
        step program has compiled or with the ledger off."""
        return self.comm_ledger.step_wire_bytes(
            self.gradient_accumulation_steps(),
            prefer=self._active_step_program())

    def comm_receipt(self):
        """{program, collectives, payload_bytes, wire_bytes} for ONE
        optimizer step of the program(s) currently dispatched — the
        fused step when it exists, else the step-wise programs summed
        with the micro-batch multiplicity (bench/multichip rows quote
        this next to the memory receipts); None when unrecorded."""
        return self.comm_ledger.step_entry(
            self.gradient_accumulation_steps(),
            prefer=self._active_step_program())

    def overlap_receipt(self):
        """{program, wire_seconds, exposed_wire_seconds,
        overlap_fraction} for ONE optimizer step from the comm ledger's
        compile-time overlap analysis (``profiling/overlap.py``): the
        static statement of which predicted wire seconds the compiled
        schedules actually pay as latency.  None until a program with
        an overlap summary has compiled or with the ledger off."""
        return self.comm_ledger.step_overlap(
            self.gradient_accumulation_steps(),
            prefer=self._active_step_program())

    def driver_seconds_per_step(self):
        """Steady-state host-side driver seconds per step (batch fetch
        through dispatch enqueue) — the attribution model's driver
        phase.  MIN over the recent window, not the median: the first
        dispatch of each program traces+compiles inside the same
        bracket, and on short runs (2-step dryrun legs) that spike
        would dominate any averaging estimator; a genuinely slow input
        pipeline raises every sample, so the min still carries the
        straggler signal.  0.0 until a step has run."""
        vals = self._driver_latencies.recent()
        return float(min(vals)) if vals else 0.0

    def attribution_receipt(self):
        """Reconciled step-time attribution (``profiling/attribution``):
        the predicted per-step budget — roofline compute, exposed
        collective wire, declared host stream (all from the comm
        ledger's compile-time overlap analyses), host driver time —
        next to the measured per-step p50 from the latency ring, with
        the residual as the ``unexplained`` phase and
        ``step_unexplained_fraction``.  Host arithmetic on
        already-captured scalars: ZERO device syncs (covered by the
        device_get-counting telemetry test).  None until a step program
        with an overlap analysis has compiled or with the ledger off.

        When the flops profiler has run, the receipt also carries
        ``flops_check`` — the jaxpr-counted compute term as an
        independent cross-check on the HLO roofline (>2x disagreement
        flagged)."""
        from ..profiling import attribution as attr_prof

        if not self.comm_ledger.enabled:
            return None
        budget = attr_prof.step_budget(
            self.comm_ledger.overlap_entries(),
            self.gradient_accumulation_steps(),
            prefer=self._active_step_program(),
            driver_seconds=self.driver_seconds_per_step())
        if budget is None:
            return None
        snap = self._step_latencies.latency_snapshot()
        receipt = attr_prof.reconcile(
            budget, snap["p50"] if snap["n"] else None)
        prof = (self.flops_profiler.profile
                if self.flops_profiler is not None else None)
        if prof is not None and prof.flops:
            from ..profiling.utilization import chip_specs

            specs = chip_specs(getattr(self.mesh.devices.flat[0],
                                       "device_kind", ""))
            receipt["flops_check"] = attr_prof.flops_cross_check(
                budget, prof.flops, specs["peak_tflops"] * 1e12)
        return receipt

    def _sample_attribution(self):
        """Attribution gauges + EVENT_ATTRIBUTION at the
        steps_per_print cadence.  Host arithmetic on already-recorded
        floats only — ZERO added per-step syncs (the device_get-counting
        telemetry test covers an attribution-enabled run)."""
        if not self.telemetry.enabled:
            return
        receipt = self.attribution_receipt()
        if receipt is None or receipt["measured_step_seconds"] is None:
            return
        from ..profiling import attribution as attr_prof

        for phase in attr_prof.PHASES:
            val = receipt["phases"].get(phase)
            if val is not None:
                self.telemetry.gauge(f"attribution/{phase}_seconds").set(
                    float(val))
        self.telemetry.gauge("attribution/predicted_step_seconds").set(
            float(receipt["predicted_step_seconds"]))
        self.telemetry.gauge("attribution/measured_step_seconds").set(
            float(receipt["measured_step_seconds"]))
        self.telemetry.gauge("attribution/unexplained_fraction").set(
            float(receipt["step_unexplained_fraction"]))
        self.telemetry.emit(TEL.EVENT_ATTRIBUTION,
                            step=self.global_steps, **receipt)

    # ------------------------------------------------------------------
    # program verification (deepspeed_tpu/profiling/verify, DSP6xx)
    # ------------------------------------------------------------------
    def program_verify_context(self):
        """Mesh/parameter/donation context the DSP6xx program verifier
        resolves collectives against (also serialized into the
        ``<run_dir>/programs/`` sidecars)."""
        return {
            "mesh_axes": mesh_axis_sizes(self.mesh),
            "data_axis": DATA_AXIS,
            # the flat fp32 master's footprint: the DSP611 "parameter-
            # sized payload" floor (reduced storage dtypes only shrink
            # host buffers; the flatten path stages fp32)
            "param_bytes": int(np.prod(self.flat.flat_shape)) * 4,
            "master_provenance": getattr(self.flat, "master_provenance",
                                         None),
            # overlap-analysis context (profiling/overlap, DSO7xx):
            # the per-step host-state stream the offload update moves
            # BETWEEN dispatches (serialized by construction until the
            # overlapped-streaming work lands), and the chip the
            # roofline/wire tables resolve against
            "host_state_wire_bytes": self.host_state_bytes_per_step(),
            # the declared ISSUE SCHEDULE of that stream (chunk count,
            # pipeline depth, form): what the overlap analyzer prices
            # the exposed fraction from — None means serialized-by-
            # construction (pre-overlap engines / no streaming)
            "host_stream_schedule": self.host_stream_schedule(),
            # the declared bucketed-collective schedule (overlap_comm):
            # the gradient-exchange twin of the host-stream declaration,
            # priced by the overlap analyzer on the exchange programs
            "collective_schedule": self.collective_schedule(),
            "device_kind": getattr(self.mesh.devices.flat[0],
                                   "device_kind", ""),
            # the declared SHARDING spec (profiling/sharding, DSS8xx):
            # per-family global-byte leaves with the divisors the jits
            # were built with, reconciled against the compiled entry
            # layouts — the static ÷dp residency receipt
            "declared_sharding": self._declared_sharding(),
        }

    def _declared_sharding(self):
        """The engine-declared sharding spec the DSS8xx auditor
        reconciles compiled entry layouts against: per-family
        (params / master / optimizer) global-byte leaves carrying the
        mesh axes and shard divisors of the very PartitionSpec tuples
        the jits were built with.  Fail-soft (None on any surprise):
        a declaration bug must degrade to UNVERIFIED — DSS804's job —
        never take a run down."""
        from ..profiling import sharding as sharding_prof
        try:
            mesh_axes = {str(a): int(n)
                         for a, n in mesh_axis_sizes(self.mesh).items()}
            families = {}
            m_axes, m_div = sharding_prof.spec_axes_and_divisor(
                self.flat.master_sharding.spec, mesh_axes)
            if self.zero_stage >= 3:
                # stage 3: parameters never persist — the step consumes
                # the ÷dp-sharded flat fp32 master directly and
                # re-gathers leaves per use, so the "params" family IS
                # the master buffer (the ÷dp residency claim DSS801/
                # DSS803 verify).  A separate "master" family would
                # double-claim the same entry tensor in the greedy
                # byte matcher.
                families["params"] = sharding_prof.build_declared_family(
                    (int(arr.size) * np.dtype(arr.dtype).itemsize,
                     m_axes, m_div)
                    for arr in jax.tree_util.tree_leaves(
                        self.state["master"]))
            else:
                # params: the module weights exactly as the jits consume
                # them (compute dtype), on the specs the engine placed
                # them
                spec_leaves = jax.tree_util.tree_leaves(
                    self._param_specs, is_leaf=lambda x: isinstance(x, P))
                tmpl_leaves = jax.tree_util.tree_leaves(
                    self._param_template)
                if len(spec_leaves) == len(tmpl_leaves):
                    families["params"] = \
                        sharding_prof.build_declared_family(
                            (int(np.prod(t.shape))
                             * np.dtype(t.dtype).itemsize,
                             *sharding_prof.spec_axes_and_divisor(
                                 s, mesh_axes))
                            for t, s in zip(tmpl_leaves, spec_leaves))
                # master: the flat fp32 buffer(s) under master_sharding
                families["master"] = sharding_prof.build_declared_family(
                    (int(arr.size) * np.dtype(arr.dtype).itemsize,
                     m_axes, m_div)
                    for arr in jax.tree_util.tree_leaves(
                        self.state["master"]))
            # optimizer: read the live shardings (flat buffers follow
            # the master, scalars replicate, per-rank optimizers
            # declare their own), never re-derived
            opt_leaves = jax.tree_util.tree_leaves(self.state["opt"])
            sh_leaves = jax.tree_util.tree_leaves(self._opt_shardings)
            if len(opt_leaves) == len(sh_leaves):
                families["optimizer"] = sharding_prof.build_declared_family(
                    (int(arr.size) * np.dtype(arr.dtype).itemsize,
                     *sharding_prof.spec_axes_and_divisor(
                         getattr(sh, "spec", None), mesh_axes))
                    for arr, sh in zip(opt_leaves, sh_leaves))
            # tag from the non-trivial axes; a fully trivial mesh (the
            # dp=1 offload fixture) reads "data1", never an empty part
            tag_axes = mesh_axes or {"data": 1}
            tag = (f"zero{self.zero_stage}"
                   + ("-offload" if self._offload else "") + "|"
                   + "x".join(f"{a}{n}"
                              for a, n in sorted(tag_axes.items())))
            return {"tag": tag, "mesh_axes": mesh_axes,
                    "families": families}
        except Exception as e:
            logger.debug("declared_sharding unavailable: %s", e)
            return None

    def verify_programs(self):
        """Run the DSP6xx program-level verifier (donation/aliasing +
        collective semantics, ``tools/dslint/programs.py``) over every
        program the ledger has compiled so far.  Compile-time artifacts
        only — zero device syncs, nothing on the step path.  Returns
        ``{programs_checked, violations, downgraded, diagnostics}``;
        None when the ledger kept no compiled executables.  In plan
        mode (``aot_plan=True``) the capacity planner calls this after
        ``aot_compile_train_step`` so a donation or mesh-axis bug fails
        the plan, not the 2-AM run."""
        from ..profiling.verify import verify_engine_programs

        return verify_engine_programs(self)

    def program_scopes(self):
        """``{module name as a device trace prints it ("jit_train_step"):
        {instruction: (scope path, direction)}}`` of every program the
        memory ledger has compiled so far (``telemetry/scopes.py``): the
        join a trace needs to be read by the program's own scopes.  Built
        when asked, from the compiled programs' text — nothing at set-up,
        nothing on the step path; empty where the ledger keeps no program
        (``profiling.memory_ledger``, on with telemetry)."""
        from ..telemetry import scopes

        return scopes.program_scopes(self.memory_ledger.compiled_programs())

    def _sample_comm_skew(self):
        """Per-rank step-latency export + cross-rank skew at the
        steps_per_print cadence.  Everything here is host arithmetic on
        already-recorded floats plus one tiny atomic file write/read of
        run-dir artifacts — no device access, ZERO added per-step syncs
        (the device_get-counting telemetry test covers a comm-enabled
        run; dslint DSH205 pins this to the print cadence statically)."""
        if self._step_latencies is None or not self.telemetry.enabled:
            return
        from ..profiling import comm as comm_prof

        snap = self._step_latencies.latency_snapshot()
        if not snap["n"]:
            return
        for key in ("last", "mean", "p50", "p95", "max"):
            self.telemetry.gauge(f"comm/latency/{key}_secs").set(snap[key])
        wire = self.comm_wire_bytes_per_step()
        if wire is not None:
            self.telemetry.gauge("comm/step_wire_bytes").set(float(wire))
        self.telemetry.emit(TEL.EVENT_COMM, step=self.global_steps,
                            kind=comm_prof.KIND_LATENCY, **snap)
        rank = self.telemetry.rank
        comm_prof.publish_rank_latency(self.telemetry.run_dir, rank, snap,
                                       step=self.global_steps)
        # staleness guards: a sibling is "live" if it published within
        # ~20 of OUR publish intervals (generous for slow cadences,
        # floor 10 min), and its rank must fit this run's world size —
        # files left by a previous/larger run in the same dir must not
        # raise stragglers for ranks that no longer exist
        publish_interval = max(self.steps_per_print(), 1) * snap["p50"]
        skew = comm_prof.fleet_skew(comm_prof.read_fleet_latencies(
            self.telemetry.run_dir,
            max_age_secs=max(600.0, 20.0 * publish_interval),
            world_size=self.world_size))
        if skew is None:
            return
        self.telemetry.gauge("comm/skew/slowest_over_median").set(
            float(skew["ratio"]))
        self.telemetry.gauge("comm/skew/ranks").set(float(skew["ranks"]))
        self.telemetry.emit(TEL.EVENT_COMM, step=self.global_steps,
                            kind=comm_prof.KIND_SKEW, **skew)
        factor = self.resilience_config.straggler_factor
        if (factor > 0 and skew["ranks"] >= 2
                and skew["ratio"] >= factor):
            # the resilience hook: a sick rank becomes a structured
            # anomaly event (and the resilience/anomalies counter), the
            # same stream rollback/divergence verdicts land in
            self._telemetry_anomaly(
                self.global_steps, "straggler",
                f"rank {skew['slowest_rank']} p50 "
                f"{skew['slowest']:.4f}s vs fleet median "
                f"{skew['median']:.4f}s (x{skew['ratio']:.2f} >= "
                f"straggler_factor {factor:g})")

    # ------------------------------------------------------------------
    # memory observability (deepspeed_tpu/profiling/memory)
    # ------------------------------------------------------------------
    def _host_buffer_families(self):
        """{family: [buffers]} over every pinned-host array the offload
        layout holds: the flat master, each flat optimizer leaf, the
        host gradient buffer, and any error-feedback residuals — each a
        row-group tuple under the coordinator's shared layout."""
        families = {}

        def add(family, val):
            if val is None:
                return
            for g in (val if type(val) is tuple else (val,)):
                families.setdefault(family, []).append(g)

        add("master", self.state.get("master"))
        flat, _ = jax.tree_util.tree_flatten_with_path(
            self.state.get("opt"))
        for path, leaf in flat:
            if getattr(leaf, "ndim", 0) == 2 and leaf.shape[-1] == LANES:
                key = tree_path_key(path).lstrip("/")
                parts = key.split("/")
                # row-group tuples flatten to <leaf>/<index>; fold the
                # group members back into one family
                if parts[-1].isdigit():
                    key = "/".join(parts[:-1])
                families.setdefault(f"opt/{key}", []).append(leaf)
        add("grads", self.state.get("hostgrad"))
        for name, val in (self.state.get("qres") or {}).items():
            add(f"qres/{name}", val)
        return families

    def _register_host_buffers(self):
        """Feed the ledger's host-buffer registry from the live offload
        state and publish it (one memory event + gauges).  Build-time
        only — never on the step path."""
        from .zero.coordinator import MAX_HOST_BUFFERS

        registry = self.memory_ledger.host_buffers
        for family, bufs in self._host_buffer_families().items():
            registry.register(
                family, len(bufs),
                sum(int(b.size) * b.dtype.itemsize for b in bufs),
                str(bufs[0].dtype))
        bounds, groups_per_family = self.flat.host_buffer_layout()
        state_families = [e for e in registry.entries()
                         if e["family"] == "master"
                         or e["family"].startswith("opt/")]
        state_only = sum(e["count"] for e in state_families)
        if state_only > MAX_HOST_BUFFERS:
            logger.warning(
                "host-buffer registry: %d state buffers exceed the "
                "MAX_HOST_BUFFERS=%d layout cap (%d group(s) x %d "
                "family(ies)) — expect AOT-helper instability",
                state_only, MAX_HOST_BUFFERS, groups_per_family,
                len(state_families))
        self.memory_ledger.record_host_buffers(
            bytes_per_step=self._host_state_bytes_per_step)

    def _sample_memory_watermarks(self):
        """Live HBM watermarks + host-buffer bytes at the steps_per_print
        cadence.  ``memory_stats()`` is a host-side runtime query — no
        program dispatch, no ``device_get`` — so this adds ZERO per-step
        host syncs (the device_get-counting telemetry test covers a
        memory-enabled run; dslint DSH204 guards the cadence)."""
        if not self._memory_watermarks or not self.telemetry.enabled:
            return
        from ..profiling.memory import KIND_WATERMARK, device_memory_summary

        summary = device_memory_summary()
        if summary["reporting"]:
            self.telemetry.gauge("memory/device_bytes_in_use").set(
                float(summary["bytes_in_use"]))
            self.telemetry.gauge("memory/device_peak_bytes_in_use").set(
                float(summary["peak_bytes_in_use"]))
            self.telemetry.gauge("memory/device_bytes_limit").set(
                float(summary["bytes_limit"]))
        self.telemetry.emit(
            TEL.EVENT_MEMORY, step=self.global_steps, kind=KIND_WATERMARK,
            bytes_in_use=summary["bytes_in_use"],
            peak_bytes_in_use=summary["peak_bytes_in_use"],
            bytes_limit=summary["bytes_limit"],
            devices=summary["devices"], reporting=summary["reporting"],
            host_buffer_bytes=self.memory_ledger.host_buffers.total_bytes())

    def _step_scalars(self, scale, skipped, ustep):
        """The step's scalar state, replicated over the mesh as the step
        hands it back.  A scalar made off the mesh has another type (its
        aval names no mesh) than the same scalar as the first step
        returns it, so the SECOND ``train_batch`` of a process traced,
        lowered and compiled the whole step again: 63–69 s cold and 5–10 s
        warm of BERT-large's set-up (PERF.md section 6, PR 37).  Plan mode
        (``aot_plan``) holds shapes for devices it may not have: as made."""
        scalars = {"scale": scale, "skipped": skipped, "ustep": ustep}
        if self._aot_plan:
            return scalars
        return jax.device_put(scalars, NamedSharding(self.mesh, P()))

    def aot_lower_train_step(self, sample_batch):
        """Lower (trace + StableHLO emission) the fused train-step
        program without compiling or running it — abstract avals only,
        nothing model-sized materializes.  The compile-scale guards
        inspect the returned ``Lowered``'s program text; the capacity
        planner compiles it via :meth:`aot_compile_train_step`."""
        from ..profiling.memory import _LedgeredJit

        acc = self.gradient_accumulation_steps()
        packed_host, spec = _pack_batches([sample_batch] * acc)
        batch_sharding = NamedSharding(self.mesh, P(None, DATA_AXIS, None))
        packed_sds = {k: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                              sharding=batch_sharding)
                      for k, v in packed_host.items()}
        if self.zero_stage >= 3:
            params_arg = None
        elif self._module_params is not None:
            params_arg = self._module_params
        else:
            # abstract module params (plan mode never materializes them)
            cast = self._cast_params_fn
            cast = cast.wrapped if isinstance(cast, _LedgeredJit) else cast
            params_arg = jax.eval_shape(cast, self.state["master"])
        fn = self._train_step_fn
        raw = fn.wrapped if isinstance(fn, _LedgeredJit) else fn
        with self.mesh:
            return raw.lower(
                self.state["master"], self.state["opt"], self.state["scale"],
                self.state["skipped"], self.state["ustep"], params_arg,
                packed_sds, spec, self._device_hyperparams(),
                self._segment_ids, self._extra_kwargs(),
                self.state.get("hostgrad"), self.state.get("qres"))

    def aot_compile_train_step(self, sample_batch):
        """Lower + compile the fused train-step program WITHOUT running
        it, and record its ``memory_analysis()`` in the ledger.

        ``sample_batch`` is one host micro-batch pytree of the training
        shapes (numpy; nothing is transferred).  State/optimizer
        arguments lower from the engine's real (host-resident, under
        offload) buffers, module params from their abstract shapes — so
        with ``aot_plan=True`` nothing model-sized ever lands in device
        memory.  Returns ``(compiled, ledger_entry)``; the entry is None
        when the backend lacks ``memory_analysis``.  The AOT capacity
        planner's core (``python -m deepspeed_tpu.profiling.capacity``);
        warm under the persistent compile cache."""
        lowered = self.aot_lower_train_step(sample_batch)
        with self.mesh:
            compiled = lowered.compile()
        entry = self.memory_ledger.record("train_step", compiled)
        return compiled, entry

    def close(self):
        """Flush + close every telemetry sink (events, trace, metrics
        snapshot, monitor) and stop the fleet-heartbeat monitor.
        Idempotent; also registered via atexit, so a normally-exiting
        run keeps its tail events without calling this."""
        from .compilation import uninstall_compile_telemetry

        if self._fleet_heartbeat is not None:
            self._fleet_heartbeat.stop()
        uninstall_compile_telemetry(self.telemetry)
        self.telemetry.close()

    # ------------------------------------------------------------------
    # construction helpers
    # ------------------------------------------------------------------
    def _make_opt_shardings(self):
        """Optimizer-state shardings: flat buffers follow the master's
        sharding; scalars (step counters) replicate.  Optimizers with
        per-rank state (1-bit Adam error feedback) declare their own."""
        if hasattr(self.optimizer, "state_shardings"):
            return self.optimizer.state_shardings(
                self.mesh, self.flat.master_sharding, self.flat.replicated)
        opt_shape = jax.eval_shape(
            self.optimizer.init_state,
            jax.ShapeDtypeStruct(self.flat.flat_shape, jnp.float32))
        if self.flat.host_group_bounds is not None:
            # grouped state: one sharding per row-group buffer
            return jax.tree_util.tree_map(
                lambda l: (tuple(self.flat.master_sharding
                                 for _ in self.flat.host_group_bounds)
                           if l.shape == self.flat.flat_shape
                           else self.flat.replicated),
                opt_shape)
        return jax.tree_util.tree_map(
            lambda l: self.flat.master_sharding if l.ndim > 0 else self.flat.replicated,
            opt_shape)

    def _resolve_comm_overlap(self, zc, client_optimizer):
        """Resolve ``zero_optimization.overlap_comm`` (auto|true|false)
        against what the bucketed exchange supports.  Returns
        ``(enabled, unsupported_reason)``: ``unsupported_reason`` is
        None exactly when the bucketed exchange COULD run here — the
        engine still declares the (serialized) collective schedule for
        the overlap analyzer in that case even when the answer is off,
        so the A/B control carries its receipt."""
        reason = None
        shape = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        if self.zero_stage not in (2, 3):
            reason = (f"requires ZeRO stage 2 or 3 (the sharded-gradient "
                      f"exchange rides the shard-major flat layout; "
                      f"stage={self.zero_stage})")
        elif self.dp_world_size <= 1:
            reason = ("requires dp > 1 (a single data group has no "
                      "gradient exchange to overlap)")
        elif any(sz > 1 for ax, sz in shape.items() if ax != "data"):
            reason = (f"requires a pure data-parallel mesh (got "
                      f"{shape}); model/pipe/seq/expert axes keep the "
                      f"GSPMD exchange")
        elif zc.cpu_offload:
            reason = ("does not compose with cpu_offload (the streamed "
                      "update owns the flat chunk layout)")
        elif self._config.sparse_gradients_enabled:
            reason = ("does not compose with sparse_gradients (its "
                      "shard_map step owns the gradient exchange)")
        else:
            if client_optimizer is not None:
                opt_ok = (type(client_optimizer).__name__ == "FusedAdam"
                          and not getattr(client_optimizer,
                                          "needs_segment_ids", False))
            else:
                name = (self._config.optimizer_name
                        or C.ADAM_OPTIMIZER).lower()
                opt_ok = name in (C.ADAM_OPTIMIZER, "adamw")
            if not opt_ok:
                reason = ("requires the flat Adam/AdamW optimizer (the "
                          "per-bucket update must be elementwise; LAMB "
                          "trust ratios and segment-aware optimizers "
                          "need the whole buffer)")
        cfg = zc.overlap_comm
        if cfg is False:
            return False, reason
        if cfg is True:
            if reason is not None:
                raise ValueError(
                    f"zero_optimization.overlap_comm: true but the "
                    f"bucketed exchange {reason}")
            return True, None
        return reason is None, reason

    def _configure_basic_optimizer(self, client_optimizer):
        if client_optimizer is not None:
            if hasattr(client_optimizer, "init_state") and hasattr(client_optimizer, "update"):
                if (self.zero_stage >= 1
                        and not self._config.zero_allow_untested_optimizer
                        and type(client_optimizer).__name__ not in (
                            "FusedAdam", "FusedLamb", "DeepSpeedCPUAdam")):
                    # reference gate: ZeRO is validated against its own
                    # optimizers; client optimizers need the explicit
                    # zero_allow_untested_optimizer opt-in
                    # (zero/utils.py:26, engine.py:672-712)
                    raise ValueError(
                        "ZeRO with a client optimizer requires "
                        '"zero_allow_untested_optimizer": true')
                return client_optimizer
            raise TypeError(
                "client optimizer must implement init_state/update/hyperparams "
                "(flat-optimizer protocol)")
        name = self._config.optimizer_name
        params = dict(self._config.optimizer_params or {})
        params.pop(C.MAX_GRAD_NORM, None)
        if name is None:
            name = C.ADAM_OPTIMIZER
        name = name.lower()
        if name in (C.ADAM_OPTIMIZER, "adamw"):
            return FusedAdam(adam_w_mode=(name == "adamw" or params.pop("adam_w_mode", True)),
                             **params)
        if name in ("cpuadam", "cpu_adam", "deepspeedcpuadam"):
            from ..ops.adam.cpu_adam import DeepSpeedCPUAdam

            shard_axis = "data" if (self.zero_stage >= 1
                                    and self.dp_world_size > 1) else None
            return DeepSpeedCPUAdam(shard_axis=shard_axis, mesh=self.mesh,
                                    **params)
        if name == C.LAMB_OPTIMIZER:
            return FusedLamb(**params)
        if name == C.ONEBIT_ADAM_OPTIMIZER:
            from ..runtime.fp16.onebit_adam import OnebitAdam

            return OnebitAdam(deepspeed=self, **params)
        raise ValueError(f"Unknown optimizer {name!r}")

    def _configure_lr_scheduler(self, client_scheduler):
        if client_scheduler is not None:
            return client_scheduler
        name = self._config.scheduler_name
        if name is None:
            return None
        if name not in SCHEDULE_CLASSES:
            raise ValueError(f"Unknown lr schedule {name!r}")
        sched = SCHEDULE_CLASSES[name](self.optimizer,
                                       **(self._config.scheduler_params or {}))
        log_dist(f"DeepSpeed using configured LR scheduler = {name}", ranks=[0])
        return sched

    # ------------------------------------------------------------------
    # jitted step construction
    # ------------------------------------------------------------------
    def _build_step_functions(self):
        mesh = self.mesh
        grad_sharding = self.flat.grad_sharding
        master_sharding = self.flat.master_sharding
        param_shardings = jax.tree_util.tree_map(
            lambda spec: NamedSharding(mesh, spec), self._param_specs)
        # PipelineEngine sets _grad_divisor=1: its apply() already averages
        # the loss over micro-batches inside the compiled schedule.
        grad_acc = float(getattr(self, "_grad_divisor", None)
                         or self.gradient_accumulation_steps())
        stage3 = self.zero_stage >= 3
        fp16 = self._config.fp16_enabled
        # Resilience guard: with the subsystem enabled the step computes
        # the non-finite-gradient flag for EVERY precision (the fp16
        # loss-scaler's overflow check, generalized) and skips the
        # optimizer update on it — a NaN burst can never contaminate the
        # master weights or optimizer moments.  All device-side: the flag
        # rides the step outputs and the host fetches it in the same
        # batched transfer fp16 already paid for (no new host syncs).
        guard_on = bool(self.resilience_config.enabled)
        skip_bad = fp16 or guard_on
        clip = float(self._config.gradient_clipping or 0.0)
        # Flat-gradient dtype: gradients leave the backward in the compute
        # dtype already and the flatten only concatenates, so when nothing
        # will SUM in the flat buffer — no cross-replica reduction
        # (dp == 1) and no micro-batch accumulation (acc == 1) — keeping
        # it in the compute dtype halves the flatten+update HBM traffic.
        # Values are identical for unclipped runs (bf16→fp32 casts are
        # exact; the loss scale is a power of two so the fp16 unscale
        # multiply is exact); with clipping on, the coef multiply rounds
        # once in the compute dtype — the reference's fp16 grads round
        # the same way (its grads are fp16 through unscale+clip too).
        grad_flat_dtype = jnp.float32
        if (self.compute_dtype is not None and self.dp_world_size == 1
                and self.gradient_accumulation_steps() == 1
                and not self._offload):
            grad_flat_dtype = self.compute_dtype
        scale_args = self._config.dynamic_loss_scale_args or {}
        dynamic = self.dynamic_loss_scale_enabled
        optimizer = self.optimizer
        segments = self.segments
        # No built-in optimizer needs the element-level segment_ids buffer
        # on device anymore (FusedLamb reads the static row layout from the
        # segments descriptor — an int32 buffer the size of the master copy
        # was ~33% extra optimizer-state HBM); client optimizers that ask
        # for it via a `needs_segment_ids` attribute still get it.
        self._segment_ids = None
        if getattr(optimizer, "needs_segment_ids", False):
            self._segment_ids = jax.device_put(
                segments.segment_ids(), self.flat.master_sharding)

        # ZeRO-Offload (zero/offload.py): the compiled programs stream
        # the pinned-host state to device themselves and the
        # out_shardings pin results back to host.  On backends without
        # in-jit placement the engine parks state eagerly between steps.
        offload = self._offload and not self._offload_eager  # in-jit mode
        dev_sharding = self.flat.master_device_sharding
        master_out_sharding = (self.flat.master_sharding
                               if not self._offload_eager
                               else dev_sharding)
        if self.flat.host_group_bounds is not None:
            # grouped master: one host sharding per row-group buffer
            master_out_sharding = tuple(
                self.flat.master_sharding
                for _ in self.flat.host_group_bounds)
        opt_out_shardings = (self._opt_shardings if not self._offload_eager
                             else self._opt_shardings_device)

        def to_device(flat_buf):
            return jax.device_put(flat_buf, dev_sharding) if offload else flat_buf

        # ONE object decides the update's form, builds the traced
        # functions composed below and declares their schedule
        from .zero.offload import OffloadStream

        ofs = self._offload_stream = OffloadStream(
            self._config.zero_config, self.flat, segments, optimizer,
            mesh.devices.flat[0], offload=self._offload,
            eager=self._offload_eager, host_grads=self._offload_grads,
            prng_impl=self._prng_impl, skip_bad=skip_bad, clip=clip,
            compute_dtype=self.compute_dtype,
            param_template=self._param_template,
            param_shardings=param_shardings)
        offload_stream = ofs.stream
        # the floor this device gave (chip_smoke.py prints it)
        self.offload_stream_min_bytes = ofs.stream_min_bytes
        self._state_quant = ofs.quant
        self._offload_uniform = ofs.uniform
        self._offload_overlap = ofs.overlap
        self._offload_prefetch_depth = ofs.prefetch_depth
        self._host_stream_schedule = ofs.schedule()
        self._host_state_bytes_per_step = ofs.host_state_bytes_per_step
        if self.telemetry.enabled and offload_stream:
            self.telemetry.gauge("offload/overlap_enabled").set(
                float(ofs.overlap))
            self.telemetry.gauge("offload/prefetch_depth").set(
                float(ofs.prefetch_depth))
        if self.telemetry.enabled and offload:
            self.telemetry.gauge("offload/host_state_bytes_per_step").set(
                float(ofs.host_state_bytes_per_step))

        # Declared collective schedule (profiling/overlap, DSO7xx): the
        # bucketed-exchange twin of the host-stream declaration above.
        # Whenever the bucketed exchange is SUPPORTED here (stage-2
        # pure-dp mesh, flat Adam, no offload/sparse) the engine
        # declares the bucket geometry it would build — with
        # ``overlap`` recording whether it actually did — so the
        # overlap analyzer can price the exposed fraction: pipelined =
        # fill/drain exposed and steady-state buckets hidden up to the
        # independent-compute window; serialized control = the full
        # wire exposed with the POTENTIAL window recorded (what the
        # bucketed schedule could have hidden — the DSO701 message).
        self._collective_schedule = None
        if self._comm_overlap or self._comm_overlap_unsupported is None:
            pplan = bucket_plan_decl = self.flat.bucket_plan
            if bucket_plan_decl is None:
                from .zero.buckets import BucketPlan

                pplan = BucketPlan(
                    list(self.segments.sizes), dp=self.dp_world_size,
                    reduce_bucket_size=(
                        self._config.zero_config.reduce_bucket_size),
                    allgather_bucket_size=(
                        self._config.zero_config.allgather_bucket_size))
            sched = pplan.schedule()
            sched["overlap"] = bool(self._comm_overlap)
            # fp32 flat payloads: the reduce-scatter side moves the
            # gradient buffer, the all-gather side the updated master
            sched["grad_bytes"] = int(pplan.rows * LANES * 4)
            sched["gather_bytes"] = int(pplan.rows * LANES * 4)
            if self.zero_stage >= 3:
                # stage 3: parameters gather per group in the forward
                # AND re-gather in the backward (jax.checkpoint remat —
                # the freed-after-use trade), so the gather side moves
                # twice the flat buffer per step; the gradient
                # reduce-scatter is the all_gather transpose (same
                # bucket geometry, no separate schedule)
                sched["param_gathers"] = True
                sched["gather_bytes"] = int(2 * pplan.rows * LANES * 4)
            self._collective_schedule = sched
            if self.telemetry.enabled:
                self.telemetry.gauge("comm/overlap_comm_enabled").set(
                    float(bool(self._comm_overlap)))
                self.telemetry.gauge("comm/reduce_buckets").set(
                    float(sched["rs_buckets"]))
                self.telemetry.gauge("comm/allgather_groups").set(
                    float(sched["ag_buckets"]))

        def scale_tail(scale_state, skipped, overflow):
            """(loss-scale state, skipped count) after a step that
            overflowed or not."""
            if fp16 and dynamic:
                scale_state = update_scale_state(
                    scale_state, overflow,
                    scale_window=scale_args.get("scale_window", 1000),
                    min_scale=scale_args.get("min_scale", 1.0),
                    delayed_shift=scale_args.get("delayed_shift", 1))
            if skip_bad:
                skipped = skipped + overflow.astype(jnp.int32)
            return scale_state, skipped

        @jax.named_scope("optimizer")
        def apply_update_hostg(master, opt_state, scale_state, skipped,
                               hostg, sq, finite, hp, qres=None):
            """The offload_gradients update: gradients stream back from
            the pinned-host buffer per chunk; unscale + clip fold into a
            single per-chunk multiply (``coef``)."""
            inv = 1.0 / scale_state.cur_scale
            overflow = (jnp.logical_not(finite) if skip_bad
                        else jnp.asarray(False))
            if clip > 0.0:
                gnorm = jnp.sqrt(sq) * inv
                coef = inv * jnp.minimum(1.0, clip / (gnorm + 1e-6))
            else:
                gnorm = jnp.asarray(0.0, jnp.float32)
                coef = jnp.asarray(inv, jnp.float32)
            new_master, new_opt, qres, cast_list = ofs.update(
                master, opt_state, hostg, hp, overflow, qres=qres,
                coef=coef, g_on_host=True, want_cast=True)
            scale_state, skipped = scale_tail(scale_state, skipped, overflow)
            return (new_master, new_opt, scale_state, skipped, overflow,
                    gnorm, qres, cast_list)

        @jax.named_scope("cast_params")
        def cast_params(master):
            if self._comm_overlap:
                # bucketed overlap_comm layout: per-allgather-group
                # gathers in a manual region (helpers defined below in
                # this scope; tracing happens after the whole builder
                # ran, so the late binding is safe)
                leaves = shard_map(
                    lambda m: _gather_cast_leaves(m), mesh=mesh,
                    in_specs=(P(DATA_AXIS),),
                    out_specs=tuple(rep_spec for _ in ag_templates),
                    axis_names={DATA_AXIS}, check_vma=False)(master)
                params = jax.tree_util.tree_unflatten(param_treedef,
                                                      list(leaves))
                return jax.tree_util.tree_map(
                    lambda x, s: jax.lax.with_sharding_constraint(x, s),
                    params, param_shardings)
            # stage 3 skips the up-front full replication: each leaf's row
            # slice gathers lazily from the sharded master, so XLA can
            # schedule per-layer gathers and free them after last use
            # instead of materializing a replicated copy of every
            # parameter for the whole step (stage-3's memory win)
            if offload_stream and self.compute_dtype:
                return ofs.cast(master)
            elif type(master) is tuple:
                # grouped state but fp32 compute: the full fp32 buffer is
                # needed on device regardless — assemble it
                flat_src = jnp.concatenate(
                    [jax.device_put(m_g, dev_sharding) for m_g in master],
                    axis=0)
            else:
                flat_src = to_device(master)
            params = self.flat.unflatten_params(flat_src,
                                                self._param_template,
                                                self.compute_dtype,
                                                constrain=not stage3)
            return jax.tree_util.tree_map(
                lambda x, s: jax.lax.with_sharding_constraint(x, s),
                params, param_shardings)

        self._cast_params_fn = self.memory_ledger.wrap(
            "cast_params", jax.jit(cast_params,
                                   out_shardings=param_shardings))

        sparse_paths = tuple(self._sparse_grad_paths)
        dp_size = dict(zip(mesh.axis_names, mesh.devices.shape)).get(
            DATA_AXIS, 1)

        if sparse_paths:
            # fp16's overflow-skip machinery reads any non-finite gradient
            # as an ordinary overflow and silently skips the step — it
            # would swallow the loud-NaN overflow poison below forever.
            # bf16/fp32 (the TPU-native paths) propagate NaN to the loss.
            assert not fp16, (
                "sparse_gradients does not compose with fp16 loss scaling "
                "(overflow-skip would mask budget-overflow detection); use "
                "bf16 or fp32")

        def sparse_loss_and_flat_grads(params, batch, rng, cur_scale, extra):
            """The ``sparse_gradients`` step path (reference
            ``engine.py:1203-1241``): fwd+bwd run rank-local under shard_map
            over the data axis, then declared embedding grads exchange as
            row-sparse (indices, values) pairs — ``tokens-per-local-batch``
            rows on the wire instead of ``vocab`` rows — while every other
            leaf takes an ordinary pmean.  GSPMD can't express this (its
            gradient reduction is implicit), hence the manual region.

            Semantics note: the step loss is the equal-weight pmean of the
            per-rank means.  For losses normalized by a data-dependent
            count (e.g. MLM cross entropy over per-row masked counts) this
            differs from the dense path's single global normalization
            unless every rank carries the same count — which the bing_bert
            ``max_predictions_per_seq`` data contract guarantees."""
            from .csr_tensor import CSRTensor, csr_allreduce

            def exchange(grads, batch_):
                ids = batch_.get("input_ids") if isinstance(batch_, dict) \
                    else None
                flat, treedef = jax.tree_util.tree_flatten_with_path(grads)
                out = []
                drops = {}
                for path, g in flat:
                    key = tree_path_key(path)
                    if (key in sparse_paths and g.ndim == 2
                            and ids is not None
                            and int(np.prod(ids.shape)) < g.shape[0]):
                        # tokens-per-local-batch bounds the support of a
                        # true embedding-lookup gradient.  A declared leaf
                        # whose grad is NOT row-sparse (e.g. a tied LM
                        # head: the vocab projection's backward touches
                        # every row) would overflow the budget — poison
                        # the step with NaN so it fails LOUDLY instead of
                        # training on silently truncated gradients.
                        budget = int(np.prod(ids.shape))
                        csr, dropped = CSRTensor.from_dense(
                            g, max_rows=budget, return_dropped=True)
                        summed = csr_allreduce(csr, DATA_AXIS) / dp_size
                        # psum first: the poison must be REPLICATED (the
                        # out_specs claim it), even when only a subset of
                        # ranks overflowed their local budget
                        any_dropped = jax.lax.psum(dropped, DATA_AXIS)
                        drops[key] = any_dropped
                        poison = jnp.where(any_dropped > 0, jnp.nan, 0.0)
                        out.append(summed + poison.astype(summed.dtype))
                    else:
                        out.append(jax.lax.pmean(g, DATA_AXIS))
                return jax.tree_util.tree_unflatten(treedef, out), drops

            def body(batch_, rng_, cur_scale_, extra_, params_):
                key = jax.random.fold_in(rng_, jax.lax.axis_index(DATA_AXIS))

                def scaled_loss(p):
                    loss = self._loss_fn(p, batch_, rng=key, train=True,
                                         **extra_)
                    return (loss.astype(jnp.float32) * cur_scale_) / grad_acc

                sloss, grads = jax.value_and_grad(scaled_loss)(params_)
                with jax.named_scope("grad_exchange"):
                    exchanged, drops = exchange(grads, batch_)
                return jax.lax.pmean(sloss, DATA_AXIS), exchanged, drops

            rep = P()
            sloss, grads, drops = shard_map(
                body, mesh=mesh,
                in_specs=(P(DATA_AXIS), rep, rep, rep, rep),
                out_specs=(rep, rep, rep),
                axis_names={DATA_AXIS}, check_vma=False)(
                batch, rng, cur_scale, extra, params)
            # the per-leaf drop counts flow OUT of the compiled program
            # (no device callback in the step program) and the engine
            # reports them host-side — see _check_sparse_overflow
            with jax.named_scope("grad_flatten"):
                flat_g = self.flat.flatten_grads(grads)
            flat_g = jax.lax.with_sharding_constraint(flat_g, grad_sharding)
            return sloss * grad_acc / cur_scale, flat_g, drops

        # -- bucketed gradient-collective overlap (overlap_comm) --------
        # The GSPMD fused exchange concatenates every leaf's gradient
        # and reduce-scatters the whole flat buffer at once: one
        # collective that depends on the ENTIRE backward, so nothing
        # can hide its wire (profiling/overlap classifies it
        # serialized).  Under overlap_comm the exchange becomes one
        # explicit psum_scatter per reduce_bucket_size-bounded,
        # leaf-aligned bucket inside a manual shard_map region, issued
        # in backward-production order (later layers' grads materialize
        # first) — bucket i's reduce-scatter is data-independent of the
        # still-running earlier-layer backward, so XLA's latency-hiding
        # scheduler can overlap them.  The flat buffers live in the
        # plan's shard-major sub-partition layout (zero/buckets.py):
        # each rank owns its piece of every bucket, contiguous in its
        # local shard, so the per-bucket update slices and the
        # per-group master all-gathers (allgather_bucket_size) stay
        # collective-free beyond the declared schedule.
        comm_overlap = bool(self._comm_overlap)
        # stage-3 parameter sharding rides the same shard-major bucket
        # layout: the step differentiates w.r.t. the LOCAL master shard
        # and the per-group all-gathers move INSIDE the differentiated
        # function (see zero3_loss_and_flat_grads below)
        stage3_overlap = stage3 and comm_overlap
        bucket_plan = self.flat.bucket_plan
        flat_shape = self.flat.flat_shape
        rep_spec = P()
        ag_templates = jax.tree_util.tree_leaves(self._param_template)
        _, param_treedef = jax.tree_util.tree_flatten(self._param_template)

        def bucketed_loss_and_flat_grads(params, batch, rng, cur_scale,
                                         extra):
            dp = self.dp_world_size

            def body(batch_, rng_, cur_scale_, extra_, params_):
                key = jax.random.fold_in(rng_,
                                         jax.lax.axis_index(DATA_AXIS))

                def scaled_loss(p):
                    loss = self._loss_fn(p, batch_, rng=key, train=True,
                                         **extra_)
                    return (loss.astype(jnp.float32) * cur_scale_) / grad_acc

                sloss, grads = jax.value_and_grad(scaled_loss)(params_)
                leaves = jax.tree_util.tree_leaves(grads)
                inv_dp = jnp.float32(1.0 / dp)
                pieces = [None] * bucket_plan.n_buckets
                # reversed = backward-production order: the backward
                # frees later leaves first, so the first-issued bucket
                # is ready while earlier layers still differentiate
                for bi in reversed(range(bucket_plan.n_buckets)):
                    with jax.named_scope("grad_flatten"):
                        block = bucket_plan.bucket_block_from_leaves(
                            leaves, bi, jnp.float32)
                    with jax.named_scope("grad_exchange"):
                        pieces[bi] = jax.lax.psum_scatter(
                            block, DATA_AXIS, scatter_dimension=0,
                            tiled=True) * inv_dp
                with jax.named_scope("grad_flatten"):
                    local = jnp.concatenate(pieces, axis=0)
                return jax.lax.pmean(sloss, DATA_AXIS), local

            sloss, flat_g = shard_map(
                body, mesh=mesh,
                in_specs=(P(DATA_AXIS), rep_spec, rep_spec, rep_spec,
                          rep_spec),
                out_specs=(rep_spec, P(DATA_AXIS)),
                axis_names={DATA_AXIS}, check_vma=False)(
                batch, rng, cur_scale, extra, params)
            return sloss * grad_acc / cur_scale, flat_g, {}

        def _gather_cast_leaves(m_loc, remat=False):
            """Manual-region helper: my (piece_rows, LANES) master shard
            -> every param leaf in compute dtype, ONE all_gather per
            allgather_bucket_size group — each leaf then depends only on
            its group's gather (and that gather only on its buckets'
            updated pieces), so the gathers overlap the other buckets'
            update compute.

            ``remat=True`` (the stage-3 forward) wraps each group's
            gather+carve in ``jax.checkpoint``: the gathered leaves are
            FREED after their last forward use and re-gathered on the
            backward instead of persisting as residuals, so peak param
            residency stays one-to-two groups — never the model."""
            out = [None] * len(ag_templates)
            for g_lo, g_hi in bucket_plan.ag_groups:
                lo_b = bucket_plan.buckets[g_lo]
                hi_b = bucket_plan.buckets[g_hi - 1]
                piece = jax.lax.slice_in_dim(
                    m_loc, lo_b.piece_start,
                    hi_b.piece_start + hi_b.piece_rows)

                def gather_group(piece_, g_lo=g_lo, g_hi=g_hi):
                    full = jax.lax.all_gather(piece_, DATA_AXIS, axis=0,
                                              tiled=False)
                    off = 0
                    groups = []
                    for bi in range(g_lo, g_hi):
                        b = bucket_plan.buckets[bi]
                        block = full[:, off:off + b.piece_rows].reshape(
                            b.rows, LANES)
                        off += b.piece_rows
                        groups.append(bucket_plan.carve_bucket(
                            block, bi, ag_templates, self.compute_dtype))
                    return groups
                carved_groups = (jax.checkpoint(gather_group)(piece)
                                 if remat else gather_group(piece))
                for bi, carved in zip(range(g_lo, g_hi), carved_groups):
                    b = bucket_plan.buckets[bi]
                    for k, li in enumerate(range(b.leaf_lo, b.leaf_hi)):
                        out[li] = carved[k]
            return tuple(out)

        def bucketed_update_and_cast(master, opt_state, g, hp, overflow,
                                     want_cast):
            """Per-bucket optimizer update + per-group master all-gather
            in ONE manual region, so bucket b's gather depends only on
            bucket b's update — the pipeline's drain side.  Elementwise
            math on contiguous local slices; scalars (step counter)
            update once."""
            opt_leaves, opt_def = jax.tree_util.tree_flatten(opt_state)
            flat_idx = [i for i, l in enumerate(opt_leaves)
                        if getattr(l, "shape", None) == flat_shape]
            flat_set = set(flat_idx)

            def body(m_loc, flats_loc, g_loc, overflow_, hp_):
                new_m = []
                new_flats = [[] for _ in flat_idx]
                scalars_out = None
                for b in bucket_plan.buckets:
                    lo, hi = b.piece_start, b.piece_start + b.piece_rows
                    pm = jax.lax.slice_in_dim(m_loc, lo, hi)
                    pg = jax.lax.slice_in_dim(g_loc, lo, hi)
                    lv = list(opt_leaves)
                    slices = {}
                    for k, i in enumerate(flat_idx):
                        slices[i] = jax.lax.slice_in_dim(
                            flats_loc[k], lo, hi)
                        lv[i] = slices[i]
                    st_b = jax.tree_util.tree_unflatten(opt_def, lv)
                    npm, nst = optimizer.update(st_b, pm, pg, hp_)
                    n_lv = jax.tree_util.tree_leaves(nst)
                    if skip_bad:
                        npm = jnp.where(overflow_, pm, npm)
                    new_m.append(npm)
                    for k, i in enumerate(flat_idx):
                        nv = n_lv[i]
                        if skip_bad:
                            nv = jnp.where(overflow_, slices[i], nv)
                        new_flats[k].append(nv)
                    if scalars_out is None:
                        scalars_out = []
                        for i, nv in enumerate(n_lv):
                            if i in flat_set:
                                continue
                            if skip_bad:
                                nv = jnp.where(overflow_, opt_leaves[i],
                                               nv)
                            scalars_out.append(nv)
                m_out = jnp.concatenate(new_m, axis=0)
                flats_out = tuple(jnp.concatenate(f, axis=0)
                                  for f in new_flats)
                with jax.named_scope("cast_params"):
                    cast = (_gather_cast_leaves(m_out) if want_cast
                            else ())
                return m_out, flats_out, tuple(scalars_out or ()), cast

            n_scalars = len(opt_leaves) - len(flat_idx)
            m_out, flats_out, scalars_out, cast_leaves = shard_map(
                body, mesh=mesh,
                in_specs=(P(DATA_AXIS),
                          tuple(P(DATA_AXIS) for _ in flat_idx),
                          P(DATA_AXIS), rep_spec, rep_spec),
                out_specs=(P(DATA_AXIS),
                           tuple(P(DATA_AXIS) for _ in flat_idx),
                           tuple(rep_spec for _ in range(n_scalars)),
                           tuple(rep_spec for _ in ag_templates)
                           if want_cast else ()),
                axis_names={DATA_AXIS}, check_vma=False)(
                master, tuple(opt_leaves[i] for i in flat_idx), g,
                overflow, hp)
            lv = list(opt_leaves)
            scal_iter = iter(scalars_out)
            for i in range(len(lv)):
                lv[i] = (flats_out[flat_idx.index(i)] if i in flat_set
                         else next(scal_iter))
            new_opt = jax.tree_util.tree_unflatten(opt_def, lv)
            new_params = (jax.tree_util.tree_unflatten(
                param_treedef, list(cast_leaves)) if want_cast else None)
            return m_out, new_opt, new_params

        # -- stage-3 sharded parameters (zero_stage 3 + overlap_comm) ---
        # The naive stage-3 step gathers the WHOLE flat master up front
        # (GSPMD lazy, but one fused all-gather the entire forward
        # depends on — profiling/overlap classifies it serialized).
        # Here the loss differentiates w.r.t. the local (piece_rows,
        # LANES) master shard inside ONE manual region: each allgather
        # group's parameters gather just in time in forward order —
        # group k's gather is data-independent of group k-1's compute,
        # so XLA's latency-hiding scheduler issues it early and hides
        # the wire — and jax.checkpoint around each group frees the
        # gathered leaves after last use and re-gathers on backward
        # (peak param residency = one-to-two groups, not the model).
        # The transpose of the tiled=False all_gather is exactly
        # psum_scatter, so the stage-3 gradient exchange arrives
        # reduced AND sharded with no extra collective code.
        def zero3_loss_and_flat_grads(master, batch, rng, cur_scale,
                                      extra):
            dp = self.dp_world_size

            def body(batch_, rng_, cur_scale_, extra_, m_loc):
                key = jax.random.fold_in(rng_,
                                         jax.lax.axis_index(DATA_AXIS))

                def scaled_loss(m):
                    # the per-group gathers; their transpose is the
                    # gradient's reduce-scatter
                    with jax.named_scope("grad_exchange"):
                        leaves = _gather_cast_leaves(m, remat=True)
                    p = jax.tree_util.tree_unflatten(param_treedef,
                                                     list(leaves))
                    loss = self._loss_fn(p, batch_, rng=key, train=True,
                                         **extra_)
                    return (loss.astype(jnp.float32) * cur_scale_) / grad_acc

                sloss, g_loc = jax.value_and_grad(scaled_loss)(m_loc)
                # the all_gather transpose delivers the cross-rank SUM
                # of gradient shards; ×1/dp makes it the dp mean
                return (jax.lax.pmean(sloss, DATA_AXIS),
                        g_loc * jnp.float32(1.0 / dp))

            sloss, flat_g = shard_map(
                body, mesh=mesh,
                in_specs=(P(DATA_AXIS), rep_spec, rep_spec, rep_spec,
                          P(DATA_AXIS)),
                out_specs=(rep_spec, P(DATA_AXIS)),
                axis_names={DATA_AXIS}, check_vma=False)(
                batch, rng, cur_scale, extra, master)
            return sloss * grad_acc / cur_scale, flat_g, {}

        @jax.named_scope("loss_and_grads")
        def loss_grads_and_reports(params, batch, rng, cur_scale, extra):
            """(loss, flat gradient, sparse drop counters, the model's own
            reported scalars: {} unless it has ``apply_reporting`` and the
            step takes the plain path)."""
            if sparse_paths:
                return (*sparse_loss_and_flat_grads(params, batch, rng,
                                                    cur_scale, extra), {})
            if stage3_overlap:
                # ``params`` IS the sharded flat master here — gathers
                # happen inside the differentiated body
                return (*zero3_loss_and_flat_grads(params, batch, rng,
                                                   cur_scale, extra), {})
            if comm_overlap:
                return (*bucketed_loss_and_flat_grads(params, batch, rng,
                                                      cur_scale, extra), {})

            def scaled_loss(p):
                if self._loss_reports_fn is None:
                    loss, reports = self._loss_fn(p, batch, rng=rng,
                                                  train=True, **extra), {}
                else:
                    loss, reports = self._loss_reports_fn(
                        p, batch, rng=rng, train=True, **extra)
                return ((loss.astype(jnp.float32) * cur_scale) / grad_acc,
                        reports)

            (sloss, reports), grads = jax.value_and_grad(
                scaled_loss, has_aux=True)(params)
            with jax.named_scope("grad_flatten"):
                flat_g = self.flat.flatten_grads(grads, dtype=grad_flat_dtype)
            # GSPMD places the reduce-scatter / all-reduce where the flat
            # buffer meets its sharding
            with jax.named_scope("grad_exchange"):
                flat_g = jax.lax.with_sharding_constraint(flat_g,
                                                          grad_sharding)
            loss = sloss * grad_acc / cur_scale
            return loss, flat_g, {}, reports

        @jax.named_scope("loss_and_grads")
        def loss_and_grads_tree(params, batch, rng, cur_scale, extra):
            """offload_gradients path: returns the raw gradient TREE (no
            device flatten — ofs.grads_to_host streams it out leaf-wise)."""

            def scaled_loss(p):
                loss = self._loss_fn(p, batch, rng=rng, train=True, **extra)
                return (loss.astype(jnp.float32) * cur_scale) / grad_acc

            sloss, grads = jax.value_and_grad(scaled_loss)(params)
            return sloss * grad_acc / cur_scale, grads

        def fwd_bwd(params_or_master, batch, rng, cur_scale, extra):
            # trace-time: mesh-aware ops (ring attention) resolve THIS
            # engine's mesh even when several engines coexist in-process
            set_current_mesh(mesh)
            # stage3_overlap passes the sharded master straight through:
            # zero3_loss_and_flat_grads gathers per group inside
            params = (params_or_master if not stage3 or stage3_overlap
                      else cast_params(params_or_master))
            return loss_grads_and_reports(params, batch, rng, cur_scale,
                                          extra)[:3]

        self._fwd_bwd_fn = self.memory_ledger.wrap(
            "fwd_bwd", jax.jit(
                fwd_bwd, out_shardings=(None, grad_sharding, None)))

        def accum(acc, g):
            return acc + g

        # donation metadata per jit entry point: single-sourced here so
        # the DSP6xx program verifier (profiling/verify) checks the
        # SAME donate tuples the jits were built with — an entry point
        # without donation declares an empty tuple and is exempt from
        # the DSP601 alias check
        self._donation_specs = {"cast_params": (), "fwd_bwd": (),
                                "eval_fwd": ()}

        accum_donate = (0,)
        self._donation_specs["accum"] = accum_donate
        self._accum_fn = self.memory_ledger.wrap(
            "accum", jax.jit(accum, donate_argnums=accum_donate,
                             out_shardings=grad_sharding))

        @jax.named_scope("optimizer")
        def apply_update(master, opt_state, scale_state, skipped, flat_g, hp,
                         segment_ids, qres=None, want_cast=False):
            inv = 1.0 / scale_state.cur_scale
            # .astype keeps a compute-dtype flat buffer in its dtype (a
            # traced fp32 scalar would silently promote the whole buffer)
            g = flat_g * inv.astype(flat_g.dtype)
            if skip_bad:
                overflow = jnp.logical_not(jnp.all(jnp.isfinite(flat_g)))
            else:
                overflow = jnp.asarray(False)
            if clip > 0.0:
                gnorm = jnp.sqrt(jnp.sum(g.astype(jnp.float32) ** 2))
                g = g * jnp.minimum(1.0, clip / (gnorm + 1e-6)).astype(
                    g.dtype)
            else:
                gnorm = jnp.asarray(0.0, jnp.float32)

            if comm_overlap:
                # bucketed layout: per-bucket update + per-group master
                # all-gather in one manual region (the overflow pick
                # folds in per bucket).  The scalar reductions above
                # (global gnorm/finiteness) are the mathematical
                # barrier between the reduce-scatters and the updates —
                # same caveat as the offload pipeline's clip note.
                new_master, new_opt, cast_tree = bucketed_update_and_cast(
                    master, opt_state, g, hp, overflow, want_cast)
                scale_state, skipped = scale_tail(scale_state, skipped,
                                                  overflow)
                base = (new_master, new_opt, scale_state, skipped,
                        overflow, gnorm, qres)
                return base + ((cast_tree,) if want_cast else ())

            if offload_stream:
                # streamed offload: per-chunk fp16 pick happens inside
                new_master, new_opt, qres, cast_list = ofs.update(
                    master, opt_state, g, hp, overflow, qres=qres,
                    want_cast=want_cast)
                scale_state, skipped = scale_tail(scale_state, skipped,
                                                  overflow)
                base = (new_master, new_opt, scale_state, skipped, overflow,
                        gnorm, qres)
                return base + (cast_list,) if want_cast else base

            master = to_device(master)
            opt_state = jax.tree_util.tree_map(
                lambda l: to_device(l)
                if getattr(l, "shape", ()) == self.flat.flat_shape
                else l, opt_state)

            new_master, new_opt = optimizer.update(
                opt_state, master, g, hp, segments=segments, segment_ids=segment_ids)

            if skip_bad:
                pick = lambda new, old: jnp.where(overflow, old, new)
                new_master = pick(new_master, master)
                new_opt = jax.tree_util.tree_map(pick, new_opt, opt_state)
            scale_state, skipped = scale_tail(scale_state, skipped, overflow)
            return (new_master, new_opt, scale_state, skipped, overflow,
                    gnorm, qres)

        # residual buffers live in the master's (grouped) host sharding
        qres_sharding = None
        if self.state.get("qres"):
            qres_sharding = {
                k: (tuple(master_sharding for _ in v) if type(v) is tuple
                    else master_sharding)
                for k, v in self.state["qres"].items()}
        apply_donate = (0, 1, 4) + ((7,) if self.state.get("qres")
                                    else ())
        self._donation_specs["apply_update"] = apply_donate
        self._apply_fn = self.memory_ledger.wrap(
            "apply_update", jax.jit(
                apply_update,
                donate_argnums=apply_donate,
                out_shardings=(master_out_sharding, opt_out_shardings,
                               None, None, None, None, qres_sharding)))

        def eval_fwd(params_or_master, batch, rng, extra):
            set_current_mesh(mesh)
            params = cast_params(params_or_master) if stage3 else params_or_master
            return self._loss_fn(params, batch, rng=rng, train=False, **extra)

        self._eval_fn = self.memory_ledger.wrap("eval_fwd",
                                                jax.jit(eval_fwd))

        # -- fully fused train step -------------------------------------
        # One compiled program per optimizer step: micro-batch scan
        # (fwd+bwd+grad accumulation) → unscale/clip → optimizer update →
        # bf16 param cast.  This is the latency-critical path: a single
        # dispatch instead of 2+grad_acc, with master/opt/param buffers
        # donated.  The reference pays the same cost as per-instruction
        # kernel launches + stream sync (engine.py:796-1076); under XLA the
        # whole step schedules as one program.  The rng stream derives from
        # the on-device ``ustep`` counter so no host scalar crosses the wire
        # per step; the batch arrives packed (one array per dtype, see
        # ``_pack_batches``) to pay H2D transfer latency once.
        acc_steps = int(getattr(self, "_grad_divisor", None)
                        or self.gradient_accumulation_steps())
        base_rng = self._rng

        def train_step(master, opt_state, scale_state, skipped, ustep, params,
                       packed, unpack_spec, hp, segment_ids, extra,
                       hostgrad, qres):
            set_current_mesh(mesh)
            cur_scale = scale_state.cur_scale
            # stage3_overlap: the forward consumes the sharded master
            # directly (zero3_loss_and_flat_grads gathers per group
            # just in time); naive stage 3 gathers up front via
            # cast_params' lazy GSPMD path
            if stage3:
                fwd_params = master if stage3_overlap else \
                    cast_params(master)
            else:
                fwd_params = params
            with jax.named_scope("unpack"):
                batches = _unpack_batches(packed, unpack_spec)
                rng = jax.random.fold_in(base_rng,
                                         ustep * jnp.uint32(acc_steps))

            if ofs.grads_on_host:
                # capacity path: grads stream to pinned host as the
                # backward frees them; the update streams them back per
                # chunk.  acc_steps == 1 enforced at init.
                with jax.named_scope("unpack"):
                    one = jax.tree_util.tree_map(lambda x: x[0], batches)
                loss, grads = loss_and_grads_tree(fwd_params, one, rng,
                                                  cur_scale, extra)
                with jax.named_scope("loss_and_grads"), \
                        jax.named_scope("grad_flatten"):
                    hostgrad, sq, finite = ofs.grads_to_host(grads,
                                                         hostgrad)
                del grads
                (master, opt_state, scale_state, skipped, overflow,
                 gnorm, qres, cast_list) = apply_update_hostg(
                    master, opt_state, scale_state, skipped, hostgrad, sq,
                    finite, hp, qres=qres)
                if stage3:
                    new_params = None
                elif cast_list is not None:
                    new_params = ofs.carve_leaves(cast_list)
                else:
                    new_params = cast_params(master)
                drops = {k: jnp.asarray(0, jnp.int32) for k in sparse_paths}
                return (loss, master, opt_state, scale_state, skipped,
                        ustep + jnp.uint32(1), overflow, gnorm, new_params,
                        drops, hostgrad, qres, {})

            def micro(carry, xs):
                acc, i, drops_acc = carry
                batch_i = xs
                loss, flat_g, drops, reports = loss_grads_and_reports(
                    fwd_params, batch_i, jax.random.fold_in(rng, i), cur_scale,
                    extra)
                # drops may cover a SUBSET of declared leaves (trace-time
                # conditions skip some); keep the carry structure fixed
                drops_acc = {k: (jnp.maximum(v, drops[k]) if k in drops
                                 else v)
                             for k, v in drops_acc.items()}
                with jax.named_scope("loss_and_grads"):
                    acc = acc + flat_g
                return (acc, i + 1, drops_acc), (loss, reports)

            drops0 = {k: jnp.asarray(0, jnp.int32) for k in sparse_paths}
            if acc_steps == 1:
                with jax.named_scope("unpack"):
                    one = jax.tree_util.tree_map(lambda x: x[0], batches)
                loss, flat_g, drops, reports = loss_grads_and_reports(
                    fwd_params, one, rng, cur_scale, extra)
                losses = loss[None]
                drops = {**drops0, **drops}
            else:
                (flat_g, _, drops), (losses, reports) = jax.lax.scan(
                    micro, (jnp.zeros(flat_shape, jnp.float32),
                            jnp.asarray(0, jnp.int32), drops0), batches)
                # the micro-batches' mean, as the loss
                reports = jax.tree_util.tree_map(
                    lambda x: jnp.mean(x, axis=0), reports)

            upd = apply_update(master, opt_state, scale_state, skipped,
                               flat_g, hp, segment_ids, qres=qres,
                               want_cast=(offload_stream or comm_overlap)
                               and not stage3)
            (master, opt_state, scale_state, skipped, overflow,
             gnorm, qres) = upd[:7]
            if stage3:
                new_params = None
            elif comm_overlap:
                # params carved from the update region's own per-group
                # all-gathers — bucket b's gather waited only on bucket
                # b's update, not on the whole step
                new_params = upd[7]
            elif offload_stream and upd[7] is not None:
                # params assembled from the update's own device chunks —
                # no post-update re-read of the host master
                new_params = ofs.carve_leaves(upd[7])
            else:
                new_params = cast_params(master)
            return (jnp.mean(losses), master, opt_state, scale_state, skipped,
                    ustep + jnp.uint32(1), overflow, gnorm, new_params, drops,
                    hostgrad, qres, reports)

        hostgrad_sharding = None
        if ofs.grads_on_host:
            host_grad_big = self.flat.grad_host_sharding
            hostgrad_sharding = (
                tuple(host_grad_big for _ in ofs.groups)
                if ofs.groups is not None else host_grad_big)
        donate = (0, 1, 5)
        if ofs.grads_on_host:
            donate = donate + (11,)
        if self.state.get("qres"):
            donate = donate + (12,)
        self._donation_specs["train_step"] = donate
        self._train_step_fn = self.memory_ledger.wrap(
            "train_step", jax.jit(
                train_step,
                static_argnums=(7,),
                donate_argnums=donate,
                out_shardings=(None, master_out_sharding, opt_out_shardings,
                               None, None, None, None, None,
                               None if stage3 else param_shardings, None,
                               hostgrad_sharding, qres_sharding, None)),
            static_argnums=(7,))

        # 1-bit Adam compressed phase: a second program with NO dense
        # gradient allreduce (host-side phase switch at freeze_step — the
        # analog of the reference's enable_backward_allreduce=False hook,
        # onebit_adam.py:372)
        from .fp16.onebit_adam import OnebitAdam

        self._train_step_compressed_fn = None
        if isinstance(optimizer, OnebitAdam):
            assert not self._offload, (
                "OneBitAdam does not compose with cpu_offload: its per-rank "
                "error-feedback state must stay device-resident for the "
                "compressed collective")
            assert not (fp16 and dynamic), (
                "OneBitAdam's compressed phase does not support fp16 dynamic "
                "loss scaling; use bf16 (TPU-native) or a static scale")
            if clip > 0.0:
                # momentum consensus replaces the gradient exchange, so no
                # global grad norm exists to clip against — silently
                # different behavior from the dense phase unless flagged
                logger.warning(
                    "OneBitAdam: gradient_clipping=%s applies only to the "
                    "warmup (dense) phase; the compressed phase exchanges "
                    "1-bit momenta and cannot clip by global grad norm "
                    "(matches reference onebit_adam.py behavior)", clip)
            # onebit_adam.build_compressed_step jits with
            # donate_argnums=(0, 1, 5) (master, opt state, ustep)
            self._donation_specs["train_step_compressed"] = (0, 1, 5)
            self._train_step_compressed_fn = self.memory_ledger.wrap(
                "train_step_compressed", optimizer.build_compressed_step(
                    mesh=mesh, loss_fn=self._loss_fn,
                    flat_coordinator=self.flat,
                    param_template=self._param_template,
                    compute_dtype=self.compute_dtype,
                    param_shardings=param_shardings,
                    unpack_fn=_unpack_batches,
                    acc_steps=acc_steps, base_rng=base_rng,
                    master_sharding=master_sharding,
                    opt_shardings=self._opt_shardings),
                static_argnums=(7,))

        # host pinned-buffer registry (profiling/memory): one entry per
        # buffer family, fed by the coordinator's row-group layout —
        # published as a memory event + gauges, composing with the
        # MAX_HOST_BUFFERS count cap and host_state_bytes_per_step
        if self._offload:
            self._register_host_buffers()

    @staticmethod
    def _try_host_init(model, init_rng):
        """Run ``model.init`` on the host CPU backend so fp32 init params
        never occupy HBM (the ZeRO-Offload init path).  Returns None when
        no CPU backend is available (JAX_PLATFORMS names the accelerator
        alone) — the caller falls back to device init with the
        documented ~4 bytes/param transient ceiling."""
        try:
            cpu = jax.local_devices(backend="cpu")[0]
        except Exception:
            return None
        try:
            with jax.default_device(cpu):
                return model.init(jax.device_put(init_rng, cpu))
        except Exception as e:  # pragma: no cover - backend-specific
            logger.warning(
                "cpu_offload host-side model init failed (%s); falling "
                "back to device init", e)
            return None

    def _state_memory(self, kind):
        """Eager-offload mode: move master + flat optimizer leaves between
        pinned host ('park') and device memory around compiled steps."""
        target_m = (self.flat.master_sharding if kind == "pinned_host"
                    else self.flat.master_device_sharding)
        target_o = (self._opt_shardings if kind == "pinned_host"
                    else self._opt_shardings_device)
        self.state["master"] = jax.device_put(self.state["master"], target_m)
        self.state["opt"] = jax.device_put(self.state["opt"], target_o)

    def _device_master(self):
        """The flat master where a compiled program can read it: eager
        offload parks it in pinned host memory between steps."""
        m = self.state["master"]
        if self._offload_eager and m.sharding.memory_kind == "pinned_host":
            m = jax.device_put(m, self.flat.master_device_sharding)
        return m

    def _refresh_module_params(self):
        if self.zero_stage >= 3:
            self._module_params = None
        else:
            self._module_params = self._cast_params_fn(self._device_master())

    def _forward_params(self):
        if self.zero_stage >= 3:
            return self._device_master()
        return self._module_params

    def _shard_batch(self, batch):
        """Lay a host batch onto the mesh, sharded over the data axis.
        Multi-host: ``batch`` is this process's slice (the dataloader's
        ``_process_slice`` contract) and the global array is assembled from
        the per-process shards."""
        sharding = NamedSharding(self.mesh, P(DATA_AXIS))
        multihost = jax.process_count() > 1

        def put(x):
            x = np.asarray(x)
            if multihost:
                return jax.make_array_from_process_local_data(sharding, x)
            return jax.device_put(x, sharding)

        return jax.tree_util.tree_map(put, batch)

    def _device_hyperparams(self):
        """Device-resident optimizer hyperparams, refreshed only when the
        host-side values change (LR schedules).  Avoids re-transferring a
        handful of scalars — each a host→device transfer of its own —
        every step."""
        def coerce(v):
            try:
                return float(v)  # also catches np/jnp scalars
            except (TypeError, ValueError):
                if isinstance(v, (tuple, list)):
                    return tuple(coerce(x) for x in v)
                return repr(v)

        groups = getattr(self.optimizer, "param_groups", None) or [{}]
        key = repr(sorted((k, coerce(v)) for k, v in groups[0].items()))
        cached = getattr(self, "_hp_cache", None)
        if cached is not None and cached[0] == key:
            return cached[1]
        hp = self.optimizer.hyperparams()
        self._hp_cache = (key, hp)
        return hp

    def _extra_kwargs(self):
        kwargs = {}
        if self.progressive_layer_drop:
            kwargs["pld_theta"] = jnp.asarray(
                self.progressive_layer_drop.get_theta(), jnp.float32)
        return kwargs

    def _next_rng(self):
        key = jax.random.fold_in(self._rng, self.micro_steps)
        return key

    def _check_sparse_overflow(self):
        """Host-side attribution for the sparse_gradients NaN poison: the
        compiled step returns per-leaf dropped-row counters (the step
        program carries no device callback, so the print cannot live
        in it).  Called at steps_per_print
        cadence and from save_checkpoint; also public for direct use when
        a NaN loss appears."""
        drops = getattr(self, "_last_sparse_drops", None)
        if not drops:
            return {}
        # ONE transfer for the whole counter dict (device_get takes a
        # pytree); the per-leaf form cost one blocking round-trip per
        # declared embedding (dslint DSH202)
        host_drops = jax.device_get(drops)
        vals = {k: int(np.max(v)) for k, v in host_drops.items()}
        for key, n in vals.items():
            if n > 0:
                logger.error(
                    "sparse_gradients budget overflow on leaf '%s': up to "
                    "%d rows dropped in one micro-batch (max across ranks "
                    "and accumulation micro-steps) — its gradient was "
                    "poisoned with NaN (loss will be NaN) and optimizer "
                    "moments are corrupted; restart from the last "
                    "checkpoint with this leaf removed from "
                    "sparse_gradients (or raise the token budget via a "
                    "larger micro-batch)", key, n)
        return vals

    sparse_overflow_report = _check_sparse_overflow

    # ------------------------------------------------------------------
    # train loop API (reference engine.py:796-1158)
    # ------------------------------------------------------------------
    def forward(self, batch):
        """Compute loss and gradients for one micro-batch (reference
        ``engine.py:796``).  Returns the (async) scalar loss.

        API compatibility note: the reference's ``forward`` returns model
        *outputs* and ``backward(loss)`` runs autodiff.  Under XLA the
        fused fwd+bwd program is the efficient unit, so ``forward`` already
        produces gradients (held until :meth:`backward` accumulates them)
        and the return value is the scalar loss, not intermediate outputs.
        Clients that need raw model outputs should call
        :meth:`eval_batch` / ``module.apply`` directly."""
        if self._offload_grads:
            raise RuntimeError(
                "offload_gradients supports only the fused train_batch() "
                "path (the step-wise forward/backward API would hold the "
                "full flat gradient on device)")
        if self.wall_clock_breakdown():
            self.timers("forward").start(sync=False)
        batch = self._shard_batch(batch)
        scale = self.state["scale"].cur_scale
        with self.mesh:
            loss, flat_g, drops = self._fwd_bwd_fn(self._forward_params(),
                                                   batch, self._next_rng(),
                                                   scale,
                                                   self._extra_kwargs())
        if drops:
            self._last_sparse_drops = drops
        self._pending_grads = flat_g
        self._last_loss = loss
        if self.wall_clock_breakdown():
            self.timers("forward").stop(sync=False)
        return loss

    __call__ = forward

    def backward(self, loss=None, allreduce_gradients=True):
        """Accumulate the gradients computed by :meth:`forward`
        (reference ``engine.py:852``; grads were already produced by the
        fused fwd+bwd program)."""
        assert getattr(self, "_pending_grads", None) is not None, (
            "backward() called before forward()")
        with self.mesh:
            if self._acc_grads is None:
                self._acc_grads = self._pending_grads
            else:
                self._acc_grads = self._accum_fn(self._acc_grads, self._pending_grads)
        self._pending_grads = None
        self._losses.append(self._last_loss)
        self.micro_steps += 1
        self.global_samples += self.train_micro_batch_size_per_gpu() * self.dp_world_size
        return loss

    def is_gradient_accumulation_boundary(self):
        """True when the next step() applies an update (reference
        ``engine.py:989-991``)."""
        return self.micro_steps % self.gradient_accumulation_steps() == 0

    def step(self):
        """Apply the optimizer at the accumulation boundary (reference
        ``engine.py:993-1076``)."""
        if not self.is_gradient_accumulation_boundary():
            return
        self._integrity_step_enter()
        if self.wall_clock_breakdown():
            self.timers("step").start(sync=False)
        hp = self._device_hyperparams()
        if self._offload_eager:
            self._state_memory("device")
        with self.mesh:
            (self.state["master"], self.state["opt"], self.state["scale"],
             self.state["skipped"], overflow, gnorm,
             self.state["qres"]) = self._apply_fn(
                self.state["master"], self.state["opt"], self.state["scale"],
                self.state["skipped"], self._acc_grads, hp,
                self._segment_ids, self.state.get("qres"))
            self._refresh_module_params()
        if self._offload_eager:
            self._state_memory("pinned_host")
        self._acc_grads = None
        self.global_steps += 1

        guard_action = None
        if self._guard is not None or self._config.fp16_enabled:
            # fp16 parity: the reference also syncs on the overflow flag each
            # step (CheckOverflow all_reduce, utils.py:100); scheduler must
            # not step on a skipped update (engine.py:978-986).  One batched
            # transfer also carries the guard's loss/scale scalars.
            fetch = {"overflow": overflow}
            if self._guard is not None:
                fetch["losses"] = list(self._losses)
                fetch["scale"] = self.state["scale"].cur_scale
            with self.telemetry.span("device_get", step=self.global_steps):
                stats = jax.device_get(fetch)
            self._overflow = bool(stats["overflow"])
            if self._guard is not None:
                self.telemetry.note_scale(stats["scale"],
                                          step=self.global_steps)
                mean_loss = (float(np.mean(stats["losses"]))
                             if stats["losses"] else float("nan"))
                guard_action = self._guard.observe(
                    mean_loss, self._overflow,
                    scale=float(stats["scale"]), step=self.global_steps)
        else:
            self._overflow = False
        if guard_action is not None and self._apply_guard_action(
                guard_action):
            self._losses = []
            if self.wall_clock_breakdown():
                self.timers("step").stop(sync=False)
            self._step_beat()
            return

        if self.lr_scheduler is not None and not self._overflow:
            self.lr_scheduler.step()
        if self.progressive_layer_drop:
            self.progressive_layer_drop.update_state(self.global_steps)

        if self.global_steps % self.steps_per_print() == 0:
            # ONE batched transfer for every print-cadence scalar: the
            # per-loss/per-property form cost 2 + grad_acc separate
            # blocking round-trips here (dslint DSH202/DSH203).  The
            # integrity fingerprint (a dispatched device scalar) rides
            # the same transfer: zero added host syncs
            fetch = {"losses": list(self._losses),
                     "scale": self.state["scale"].cur_scale,
                     "skipped": self.state["skipped"]}
            fp_dev = self._integrity_fingerprint_device()
            if fp_dev is not None:
                fetch["fingerprint"] = fp_dev
            # dslint: disable=DSH203 -- print cadence; cannot batch with the per-step fp16 overflow fetch above
            stats = jax.device_get(fetch)
            mean_loss = (float(np.mean(stats["losses"]))
                         if stats["losses"] else 0.0)
            lr = self.get_lr()[0] if self.optimizer.param_groups else 0.0
            scale = (float(stats["scale"]) if self._config.fp16_enabled
                     else 1.0)
            if self._config.fp16_enabled:
                self.telemetry.note_scale(scale, step=self.global_steps)
            log_dist(
                f"step={self.global_steps}, skipped={int(stats['skipped'])}, "
                f"lr={lr:.6g}, loss={mean_loss:.5f}, loss_scale={scale}",
                ranks=[0])
            self.telemetry.step_metrics(self.global_steps,
                                        self.global_samples, {
                "Train/Samples/train_loss": mean_loss,
                "Train/Samples/lr": lr,
                "Train/Samples/loss_scale": scale,
            }, skipped=int(stats["skipped"]))
            self._sample_memory_watermarks()
            self._sample_comm_skew()
            self._sample_attribution()
            self._sample_integrity(stats.get("fingerprint"))
        self._losses = []
        if self._config.memory_breakdown:
            from .utils import see_memory_usage

            see_memory_usage(f"after step {self.global_steps}", force=True)
        if self.wall_clock_breakdown():
            self.timers("step").stop(sync=False)
            self.timers.log(["forward", "step"])
        self._step_beat()

    def _apply_guard_action(self, action):
        """Escalate an anomaly-guard verdict.  Returns True when a
        rollback restored earlier state (the caller's remaining step
        bookkeeping is void); raises
        :class:`~deepspeed_tpu.resilience.constants.TrainingDivergedError`
        on abort (directly, or when rollback itself is impossible)."""
        from ..resilience.constants import TrainingDivergedError
        from ..resilience.guard import ACTION_ABORT, ACTION_ROLLBACK

        if action == ACTION_ROLLBACK:
            # a checkpoint restore (drain + verify + device_put of the
            # full state) can legitimately outlast the hang timeout;
            # disarm the watchdog AND the latency ring until the
            # caller's post-rollback beat re-arms
            self._step_beat_pause()
            reason = (f"{self._guard.consecutive_anomalies} consecutive "
                      f"anomalous step(s)")
            diverged_at = self.global_steps
            try:
                with self.telemetry.span("rollback_restore"):
                    path = self._rollback_mgr.rollback(reason=reason)
            except TrainingDivergedError as e:
                if self._watchdog is not None:
                    self._watchdog.stop()
                self.telemetry.emit(TEL.EVENT_ABORT, step=self.global_steps,
                                    reason=str(e))
                self.telemetry.flush(reason="abort")
                raise
            # global_steps is now the RESTORED step (load_checkpoint
            # rewound it); from_step names the abandoned timeline's head
            self.telemetry.emit(TEL.EVENT_ROLLBACK, step=self.global_steps,
                                from_step=diverged_at, restored_path=path,
                                reason=reason)
            self.telemetry.counter("resilience/rollbacks").inc()
            self._guard.notify_rollback()
            if self._integrity is not None:
                # the abandoned timeline's published fingerprints must
                # not stay up for peers to vote against while replay
                # heals this replica (a mixed stale/replayed window
                # could convict a rank the rollback already fixed)
                self._integrity.reset_history()
            return True
        if action == ACTION_ABORT:
            if self._watchdog is not None:
                # the abort teardown (final saves, logging, sys.exit with
                # the POISON code) must never be preempted by the
                # watchdog's RESPAWNABLE os._exit
                self._watchdog.stop()
            msg = (f"training diverged at step {self.global_steps}: "
                   f"{self._guard.consecutive_anomalies} consecutive "
                   f"anomalous step(s) under policy={self._guard.policy}; "
                   f"recent anomalies: {self._guard.recent_events()[-5:]}")
            self.telemetry.emit(TEL.EVENT_ABORT, step=self.global_steps,
                                reason=msg)
            self.telemetry.flush(reason="abort")
            raise TrainingDivergedError(msg)
        return False

    def train_batch(self, data_iter=None):
        """One full training batch = grad_acc micro steps + update
        (mirrors the pipeline engine's ``train_batch``, reference
        ``pipe/engine.py:244``).

        Runs the fully fused train-step program: one XLA dispatch per
        optimizer step (micro-batch scan + update + param cast), with the
        master/optimizer/param buffers donated.  The step-wise
        ``forward()``/``backward()``/``step()`` API remains for clients that
        drive micro-batches themselves.

        Host phases are program spans (``ds:train_batch`` ⊃
        ``batch_fetch``, ``pack``, ``device_put``, ``dispatch``,
        ``device_get``, ``cadence`` in any ``jax.profiler`` capture)."""
        with self.telemetry.span("train_batch", step=self.global_steps + 1):
            return self._train_batch(data_iter)

    def _train_batch(self, data_iter):
        if data_iter is None:
            assert self.training_dataloader is not None
            if not hasattr(self, "_train_iter"):
                self._train_iter = iter(RepeatingLoader(self.training_dataloader))
            data_iter = self._train_iter
        assert getattr(self, "_pending_grads", None) is None and \
            self._acc_grads is None, (
                "train_batch() cannot run with un-stepped forward()/backward() "
                "micro-batches pending")
        self.tput_timer.start()
        t_host0 = time.perf_counter()
        if self.wall_clock_breakdown():
            self.timers("train_batch").start(sync=False)
        acc = self.gradient_accumulation_steps()
        with self.telemetry.span("batch_fetch", step=self.global_steps + 1):
            micro_batches = [next(data_iter) for _ in range(acc)]
        self._integrity_step_enter()
        try:
            with self.telemetry.span("pack"):
                packed_host, spec = _pack_batches(micro_batches)
        except (ValueError, AssertionError):
            # ragged micro-batches (e.g. a short final batch) cannot be
            # stacked into the fused program; fall back to the step-wise
            # path, which handles them at the cost of a retrace
            if self.wall_clock_breakdown():
                self.timers("train_batch").stop(sync=False)
            return self._train_batch_stepwise(micro_batches,
                                              t_host0=t_host0)
        with self.telemetry.span("device_put"):
            sharding = NamedSharding(self.mesh, P(None, DATA_AXIS, None))
            if jax.process_count() > 1:
                packed = {
                    k: jax.make_array_from_process_local_data(sharding, v)
                    for k, v in packed_host.items()}
            else:
                packed = {k: jax.device_put(v, sharding)
                          for k, v in packed_host.items()}

        hp = self._device_hyperparams()
        step_fn = self._train_step_fn
        if (self._train_step_compressed_fn is not None
                and self.global_steps >= self.optimizer.freeze_step):
            step_fn = self._train_step_compressed_fn
        if self._offload_eager:
            self._state_memory("device")
        dispatch_span = self.telemetry.span("dispatch",
                                            step=self.global_steps + 1)
        with dispatch_span, self.mesh:
            if step_fn is self._train_step_fn:
                out = step_fn(self.state["master"], self.state["opt"],
                              self.state["scale"], self.state["skipped"],
                              self.state["ustep"], self._module_params,
                              packed, spec, hp,
                              self._segment_ids, self._extra_kwargs(),
                              self.state.get("hostgrad"),
                              self.state.get("qres"))
            else:  # 1-bit compressed program (no hostgrad leg)
                out = step_fn(self.state["master"], self.state["opt"],
                              self.state["scale"], self.state["skipped"],
                              self.state["ustep"], self._module_params,
                              packed, spec, hp,
                              self._segment_ids, self._extra_kwargs())
        # host-side driver seconds: everything from the step's start to
        # the end of the (async) dispatch enqueue — batch fetch, pack,
        # device_put, trace-or-lookup.  The blocking scalar fetch below
        # is deliberately EXCLUDED: device_get waits on the device, so
        # its duration is device time the budget's compute/wire phases
        # already predict, not driver overhead
        self._driver_latencies.record(time.perf_counter() - t_host0)
        # the regular step carries a trailing sparse-overflow counter dict
        # and the donated hostgrad buffer; the 1-bit compressed program
        # (no sparse exchange, no offload) does not
        (loss, self.state["master"], self.state["opt"], self.state["scale"],
         self.state["skipped"], self.state["ustep"], overflow, gnorm,
         new_params) = out[:9]
        if len(out) > 9 and out[9]:
            self._last_sparse_drops = out[9]
        if len(out) > 10:
            self.state["hostgrad"] = out[10]
        if len(out) > 11:
            self.state["qres"] = out[11]
        if len(out) > 12:   # device scalars: fetched at the print cadence
            self._last_reports = out[12]
        if self.zero_stage < 3:
            self._module_params = new_params
        if self._offload_eager:
            self._state_memory("pinned_host")

        self.micro_steps += acc
        self.global_samples += acc * self.train_micro_batch_size_per_gpu() \
            * self.dp_world_size
        self.global_steps += 1

        guard_action = None
        if self._guard is not None or self._config.fp16_enabled:
            # ONE batched transfer for every per-step scalar the driver
            # needs: the overflow flag (fp16 parity: the reference also
            # syncs on it each step, CheckOverflow all_reduce,
            # utils.py:100) and — guard on — the loss + loss scale the
            # anomaly guard classifies.  The guard rides the transfer
            # fp16 already paid for; it never adds a second sync.
            fetch = {"overflow": overflow}
            if self._guard is not None:
                fetch["loss"] = loss
                fetch["scale"] = self.state["scale"].cur_scale
            with self.telemetry.span("device_get", step=self.global_steps):
                stats = jax.device_get(fetch)
            # with the guard on, a skipped (non-finite) update must not
            # advance the scheduler in ANY precision, same as fp16
            self._overflow = bool(stats["overflow"])
            if self._guard is not None:
                self.telemetry.note_scale(stats["scale"],
                                          step=self.global_steps)
                guard_action = self._guard.observe(
                    float(stats["loss"]), self._overflow,
                    scale=float(stats["scale"]), step=self.global_steps)
        else:
            self._overflow = False
        if guard_action is not None and self._apply_guard_action(
                guard_action):
            # rolled back: counters, scheduler, and scale state now come
            # from the restored checkpoint; this step's remaining
            # bookkeeping belongs to the abandoned timeline
            if self.wall_clock_breakdown():
                self.timers("train_batch").stop(sync=False)
            self.tput_timer.stop()
            self._step_beat()
            return loss
        if self.lr_scheduler is not None and not self._overflow:
            self.lr_scheduler.step()
        if self.progressive_layer_drop:
            self.progressive_layer_drop.update_state(self.global_steps)

        if (self.flops_profiler is not None and self.global_steps ==
                self._config.flops_profiler_config.profile_step):
            prof = self.flops_profiler.profile_train_step(micro_batches[0])
            prof.print(
                top_modules=self._config.flops_profiler_config.top_modules)

        if self.global_steps % self.steps_per_print() == 0:
            with self.telemetry.span("cadence", step=self.global_steps):
                # monitor scalars share the steps_per_print cadence: fetching
                # them is a host sync, so it must stay off the per-step
                # critical path — and cost ONE transfer, not three (loss,
                # scale and skipped fetched separately each paid a full wire
                # round-trip; dslint DSH203)
                self._check_sparse_overflow()
                lr = self.get_lr()[0] if self.optimizer.param_groups else 0.0
                # the integrity fingerprint (a dispatched device scalar)
                # rides the same batched transfer: zero added host syncs
                fetch = {"loss": loss,
                         "scale": self.state["scale"].cur_scale,
                         "skipped": self.state["skipped"]}
                fp_dev = self._integrity_fingerprint_device()
                if fp_dev is not None:
                    fetch["fingerprint"] = fp_dev
                if self._last_reports:   # the model's own scalars ride along
                    fetch["reports"] = self._last_reports
                # dslint: disable=DSH203 -- print cadence; cannot batch with the per-step fp16 overflow fetch above
                stats = jax.device_get(fetch)
                loss_val = float(stats["loss"])
                scale = (float(stats["scale"]) if self._config.fp16_enabled
                         else 1.0)
                if self._config.fp16_enabled:
                    self.telemetry.note_scale(scale, step=self.global_steps)
                log_dist(
                    f"step={self.global_steps}, skipped={int(stats['skipped'])}, "
                    f"lr={lr:.6g}, loss={loss_val:.5f}, loss_scale={scale}",
                    ranks=[0])
                # reference tensorboard tags (engine.py:1014-1067); the event
                # stream + registry ride the same already-fetched scalars
                self.telemetry.step_metrics(self.global_steps,
                                            self.global_samples, {
                    "Train/Samples/train_loss": loss_val,
                    "Train/Samples/lr": lr,
                    "Train/Samples/loss_scale": scale,
                    **{name: float(value) for name, value in
                       stats.get("reports", {}).items()},
                }, skipped=int(stats["skipped"]))
                self._sample_memory_watermarks()
                self._sample_comm_skew()
                self._sample_attribution()
                self._sample_integrity(stats.get("fingerprint"))
        if self.wall_clock_breakdown():
            # the fused program has no forward/step boundary to time
            # separately; report the whole fused step
            self.timers("train_batch").stop(sync=True)
            self.timers.log(["train_batch"])
        self.tput_timer.stop()
        if self.telemetry.enabled:
            # O(1) host bookkeeping; host_step_secs measures the HOST side
            # of the step (dispatch is async — device time shows up here
            # only when the dispatch queue backpressures)
            self.telemetry.counter("train/steps").inc()
            self.telemetry.counter("train/samples").inc(
                acc * self.train_micro_batch_size_per_gpu()
                * self.dp_world_size)
            if self._overflow:
                self.telemetry.counter("train/overflow_steps").inc()
            self.telemetry.histogram("train/host_step_secs").observe(
                time.perf_counter() - t_host0)
            self.telemetry.poll_device_trace(self.global_steps,
                                             self.program_scopes)
        self._step_beat()
        return loss

    def _train_batch_stepwise(self, micro_batches, t_host0=None):
        """Per-micro-batch path for batches the fused program cannot take
        (ragged shapes); same semantics, more dispatches.  ``t_host0``
        is the caller's step-start perf_counter, so the attribution
        driver bracket covers batch fetch + pack like the fused path's
        (a smaller stepwise sample would win the min-window estimator
        and under-report the driver phase)."""
        # driver bracket for the attribution model: fetch/pack + the
        # fwd/bwd loop are host work (shard/put + async enqueues);
        # step()'s blocking scalar fetch stays excluded, same split as
        # the fused path
        t_drv = t_host0 if t_host0 is not None else time.perf_counter()
        losses = []
        for batch in micro_batches:
            loss = self.forward(batch)
            self.backward(loss)
            losses.append(loss)
        self._driver_latencies.record(time.perf_counter() - t_drv)
        self.step()
        self.tput_timer.stop()
        return jnp.mean(jnp.stack(losses))

    def eval_batch(self, batch):
        """Loss on one batch with ``train=False`` semantics.

        Accepts either a batch pytree (evaluated as-is) or an iterator,
        from which ``gradient_accumulation_steps`` micro-batches are drawn
        and their mean loss returned — the reference pipe engine's
        contract (``pipe/engine.py:320``: pulls ``micro_batches`` entries
        per call), so callers porting reference eval loops see the same
        iterator advancement and the same averaged loss."""
        if hasattr(batch, "__next__"):
            losses = []
            for _ in range(max(1, self.gradient_accumulation_steps())):
                try:
                    losses.append(self._eval_one(next(batch)))
                except StopIteration:
                    # dataset tail shorter than gas: average what we got
                    # rather than leaking StopIteration (PEP 479 would
                    # turn it into RuntimeError inside caller generators)
                    break
            if not losses:
                raise ValueError(
                    "eval_batch received an exhausted iterator")
            if len(losses) == 1:
                return losses[0]
            # mean over the micro-batch axis, pytree-safe (models whose
            # eval output is logits rather than a scalar loss)
            try:
                return jax.tree_util.tree_map(
                    lambda *xs: jnp.mean(jnp.stack(xs), axis=0), *losses)
            except (ValueError, TypeError) as e:
                raise ValueError(
                    "eval_batch cannot aggregate ragged per-example eval "
                    "outputs across micro-batches; pass equal-shape "
                    "micro-batches or call eval_batch per batch") from e
        return self._eval_one(batch)

    def _eval_one(self, batch):
        batch = self._shard_batch(batch)
        with self.mesh:
            return self._eval_fn(self._forward_params(), batch, self._next_rng(),
                                 self._extra_kwargs())

    # ------------------------------------------------------------------
    # data (reference engine.py:719-760)
    # ------------------------------------------------------------------
    def deepspeed_io(self, dataset, batch_size=None, route=None, pin_memory=None,
                     data_sampler=None, collate_fn=None, num_local_io_workers=None):
        batch_size = batch_size or (self.train_micro_batch_size_per_gpu()
                                    * self.dp_world_size)
        from ..parallel.mesh import data_parallel_process_info

        world, rank = data_parallel_process_info(self.mesh)
        return DeepSpeedDataLoader(
            dataset, batch_size=batch_size, collate_fn=collate_fn,
            tput_timer=self.tput_timer,
            data_parallel_world_size=world, data_parallel_rank=rank)

    # ------------------------------------------------------------------
    # checkpointing (reference engine.py:1275-1573; layout notes SURVEY §3.5)
    # ------------------------------------------------------------------
    @staticmethod
    def _path_key(path):
        """Tree path → checkpoint key.  Save and load must agree byte-for-byte."""
        return tree_path_key(path)

    def _params_to_host(self, tree):
        flat, _ = jax.tree_util.tree_flatten_with_path(tree)
        # ONE batched device→host transfer for the whole tree — the
        # per-leaf form cost one blocking round-trip per parameter leaf
        # (dslint DSH202), all while train_batch stalls behind the
        # gather.  Snapshots handed to the async writer must still own
        # their memory (CPU device_get can return a view of a donated
        # buffer), hence ensure_owned per leaf after the transfer.
        host = jax.device_get([leaf for _, leaf in flat])
        return {self._path_key(path): ensure_owned(arr)
                for (path, _), arr in zip(flat, host)}

    def save_checkpoint(self, save_dir, tag=None, client_state=None, save_latest=True,
                        sync=None):
        """Save model + optimizer + engine state (thin wrapper over
        ``deepspeed_tpu/checkpoint``).

        Layout mirrors the reference's (SURVEY §3.5): a model-states archive
        in native dtype, a ZeRO optimizer-states archive (flat master saved
        *unpadded* so a different DP degree can re-pad on load — the
        reference's elastic checkpoint trick, ``stage1.py:848-883``), a meta
        json, a checksummed ``manifest.json``, and a ``latest`` tag pointer.
        The device->host gather happens here; with ``checkpoint.async_save``
        (the default) serialization + the atomic commit run on a background
        thread and training resumes immediately.  ``sync=True`` forces an
        inline commit for this call.
        """
        self._check_sparse_overflow()
        tag = tag or f"global_step{self.global_steps}"
        with self.telemetry.span("ckpt_snapshot", tag=str(tag)):
            snapshot = capture_engine_snapshot(self, tag, client_state,
                                               save_latest)
        self._last_ckpt_dir = save_dir
        async_save = (self.checkpoint_config.async_save if sync is None
                      else not sync)
        ok = self._ckpt_manager.save(snapshot, save_dir,
                                     async_save=async_save)
        if not ok:
            # sync commits keep the old inline-save contract: I/O failure
            # raises instead of returning a flag no caller checks
            raise CheckpointError(
                f"checkpoint {tag} save to {save_dir} failed"
            ) from self._ckpt_manager.last_error
        return ok

    def wait_checkpoint(self, save_dir=None, timeout=None):
        """Block until pending async checkpoint saves finish (for
        ``save_dir``, or all of this engine's); raises
        :class:`~deepspeed_tpu.checkpoint.writer.CheckpointError` if the
        most recent commit failed.  The public way to turn an optimistic
        async ``save_checkpoint`` return into a durable guarantee."""
        return self._ckpt_manager.wait(save_dir, timeout)

    def _preemption_save(self):
        """Final synchronous save on SIGTERM, into the last save dir.
        Telemetry sinks are flushed (not closed: the previous signal
        disposition may let the process continue) so a preempted run
        keeps its tail events."""
        import signal as _signal

        self.telemetry.emit(TEL.EVENT_PREEMPTION, step=self.global_steps,
                            signum=int(_signal.SIGTERM))
        try:
            if self._last_ckpt_dir is None:
                logger.warning(
                    "preemption save skipped: no checkpoint dir seen yet "
                    "(call save_checkpoint once to set it)")
                return
            self.save_checkpoint(self._last_ckpt_dir,
                                 tag=f"global_step{self.global_steps}",
                                 sync=True)
        finally:
            self.telemetry.flush(reason="preemption")

    def load_checkpoint(self, load_dir, tag=None, load_module_strict=True,
                        load_optimizer_states=True, load_lr_scheduler_states=True,
                        strict=False):
        """Restore a checkpoint (reference ``engine.py:1275-1446``); returns
        ``(path, client_state)``.  Loading into a different DP degree re-pads
        the unpadded flat master (elastic restore, ``stage2.py:1714-1841``).

        With ``strict=False`` (default, reference behavior) a missing or
        unverifiable checkpoint warns and returns ``(None, None)``;
        ``strict=True`` raises so production resume scripts fail loudly.
        Integrity is verified against ``manifest.json`` when
        ``checkpoint.verify_on_load`` is set; pre-manifest checkpoint dirs
        load unverified with a one-line notice.
        """
        drain_inflight(load_dir)  # a same-process async save may be landing

        def _missing(msg, exc=CheckpointError):
            if strict:
                raise exc(msg)
            logger.warning(f"{msg}, cannot load")
            return None, None

        if tag is None:
            tag = ckpt.read_latest(load_dir)
            if tag is None:
                return _missing(
                    f"no '{LATEST_FILE}' file in {load_dir}")
        ckpt_dir = os.path.join(load_dir, str(tag))
        if not os.path.isdir(ckpt_dir):
            # a crash inside a same-tag re-save's rename window leaves the
            # previous committed dir parked at <tag>.old — heal it
            if not ckpt.recover_tag(load_dir, tag):
                return _missing(f"checkpoint dir {ckpt_dir} missing")
        if not os.path.isfile(os.path.join(ckpt_dir, META_JSON)):
            return _missing(f"checkpoint dir {ckpt_dir} has no {META_JSON} "
                            "(torn or foreign directory)")
        if self.checkpoint_config.verify_on_load:
            status, problems = ckpt.verify_checkpoint(ckpt_dir)
            if status == "bad":
                return _missing(f"checkpoint {ckpt_dir} failed integrity "
                                f"verification: {'; '.join(problems)}",
                                exc=CheckpointCorruptionError)
            if status == "legacy":
                logger.info(f"checkpoint {ckpt_dir} predates manifests; "
                            "loading without integrity verification")

        with open(os.path.join(ckpt_dir, META_JSON)) as f:
            meta = json.load(f)

        opt_npz = np.load(os.path.join(ckpt_dir, OPTIM_STATES_NPZ))
        # Reduced-precision offload state: checkpoints are canonical
        # fp32 (+ optional qres/<name> error-feedback residuals) and
        # load across state-dtype layouts.  Same layout -> raw buffers
        # restore bit-exactly; any other layout -> residuals fold into
        # the values, the scatter re-rounds once, and a current-layout
        # residual re-derives from the exact rounding error.
        from .zero.qstate import STATE_DTYPES

        ck_layout = meta.get("offload_state_dtype")
        qres_host = {k[len("qres/"):]: opt_npz[k]
                     for k in opt_npz.files if k.startswith("qres/")}
        sd_cur = (self._config.zero_config.offload_state_dtype
                  if self._state_reduced else None)
        name2field = {"master": "master", "exp_avg": "momentum",
                      "exp_avg_sq": "variance"}

        def _layout_match(name):
            field = name2field.get(name)
            return (field is not None and ck_layout is not None
                    and sd_cur is not None
                    and ck_layout.get("error_feedback")
                    and sd_cur["error_feedback"]
                    and ck_layout.get(field) == sd_cur[field]
                    and name in qres_host)

        def _folded(name, arr):
            # opt leaf path keys render as ".exp_avg"; qres buffers are
            # named by the bare field
            r = qres_host.get(name.lstrip("."))
            if r is None or _layout_match(name.lstrip(".")):
                return arr
            return (np.asarray(arr, np.float32)
                    + np.asarray(r, np.float32))

        with self.mesh:
            master_arr = _folded("master", opt_npz["master"])
            self.state["master"] = self.flat.scatter_master_from_unpadded(
                master_arr)
            opt_host = None
            if load_optimizer_states:
                opt_host = {k[len("opt/"):]: _folded(k[len("opt/"):],
                                                     opt_npz[k])
                            for k in opt_npz.files if k.startswith("opt/")}
                self.state["opt"] = self._restore_tree_like(
                    self.state["opt"], opt_host)
            if self.state.get("qres"):
                opt_host_n = {k.lstrip("."): v
                              for k, v in (opt_host or {}).items()}
                new_qres = {}
                for name, cur in self.state["qres"].items():
                    st_dt = STATE_DTYPES[sd_cur[name2field[name]]]
                    if _layout_match(name):
                        r_arr = np.asarray(qres_host[name], np.float32)
                    else:
                        if name == "master":
                            val = np.asarray(master_arr, np.float32)
                        elif name in opt_host_n:
                            val = np.asarray(opt_host_n[name], np.float32)
                        else:
                            # leaf state not loaded: reset the residual
                            new_qres[name] = self._scatter_flat_like(
                                cur, None)
                            continue
                        # exact rounding error of the value scatter above
                        q = val.astype(np.dtype(st_dt))
                        r_arr = val - q.astype(np.float32)
                    new_qres[name] = self._scatter_flat_like(cur, r_arr)
                self.state["qres"] = new_qres
            self._refresh_module_params()

        ss = meta["scale_state"]
        self.state.update(self._step_scalars(
            DynamicScaleState(
                cur_scale=jnp.asarray(ss["cur_scale"], jnp.float32),
                cur_iter=jnp.asarray(ss["cur_iter"], jnp.int32),
                last_overflow_iter=jnp.asarray(ss["last_overflow_iter"],
                                               jnp.int32),
                cur_hysteresis=jnp.asarray(ss["cur_hysteresis"], jnp.int32)),
            jnp.asarray(meta["skipped_steps"], jnp.int32),
            # rng-stream counter for the fused path; old checkpoints predate
            # it — fall back to global_steps (same cadence: one bump per
            # update)
            jnp.asarray(meta.get("ustep", meta["global_steps"]),
                        jnp.uint32)))
        self.global_steps = meta["global_steps"]
        self.micro_steps = meta["micro_steps"]
        self.global_samples = meta["global_samples"]
        if load_lr_scheduler_states and self.lr_scheduler is not None and meta.get(
                "lr_scheduler"):
            self.lr_scheduler.load_state_dict(meta["lr_scheduler"])

        # dataloader/sampler cursor (elastic resume contract: no replay,
        # no skip): re-arm the engine-owned loader at the checkpointed
        # stream position and drop any live iterator so the next
        # train_batch() pulls the fast-forwarded stream
        data_state = meta.get("data_state")
        if (data_state and self.training_dataloader is not None
                and hasattr(self.training_dataloader, "load_state_dict")):
            self.training_dataloader.load_state_dict(data_state)
            if hasattr(self, "_train_iter"):
                del self._train_iter

        client_state = None
        cs_path = os.path.join(ckpt_dir, CLIENT_STATE_PKL)
        if os.path.isfile(cs_path):
            with open(cs_path, "rb") as f:
                client_state = pickle.load(f)
        # a resumed job can now take its preemption save before the first
        # periodic save_checkpoint sets a directory
        self._last_ckpt_dir = load_dir
        self.telemetry.emit(TEL.EVENT_RUN_RESUME, step=self.global_steps,
                            checkpoint=ckpt_dir)
        ck_dp = meta.get("dp_world_size")
        if ck_dp is not None and int(ck_dp) != self.dp_world_size:
            # DP-elastic restore onto a different mesh shape: the
            # unpadded flat master re-partitioned over the new dp degree
            # — the resize timeline's "restore" leg
            self.telemetry.emit(TEL.EVENT_ELASTIC, step=self.global_steps,
                                phase="restore", from_dp=int(ck_dp),
                                to_dp=self.dp_world_size,
                                checkpoint=ckpt_dir)
            log_dist(
                f"elastic restore: checkpoint written at dp={ck_dp} "
                f"re-partitioned onto dp={self.dp_world_size}", ranks=[0])
        log_dist(f"loaded checkpoint {ckpt_dir}", ranks=[0])
        return ckpt_dir, client_state

    def _scatter_flat_like(self, like, arr):
        """True-sized 1-D fp32 host array -> a (possibly row-grouped)
        flat host buffer matching ``like``'s dtype/sharding/layout;
        ``arr=None`` zero-fills (residual reset)."""
        if arr is None:
            padded = np.zeros(self.flat.flat_shape, np.float32)
        else:
            padded = self.flat.repad_unpadded(np.asarray(arr).reshape(-1))
        if type(like) is tuple:
            return tuple(
                self.flat.home_host(padded[r0:r0 + rc].astype(g.dtype),
                                    g.sharding)
                for (r0, rc), g in zip(self.flat.host_group_bounds, like))
        return self.flat.home_host(padded.astype(like.dtype),
                                   like.sharding)

    def _restore_tree_like(self, tree, host_dict):
        """Place host arrays into a pytree matching ``tree``'s structure and
        shardings, keyed by tree paths.  Scalars (e.g. step counters) restore
        by shape."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(
            tree, is_leaf=lambda x: type(x) is tuple)
        leaves = []
        for path, leaf in flat:
            key = self._path_key(path)
            src = host_dict.get(key)
            assert src is not None, f"checkpoint missing key {key}"
            arr = np.asarray(src)
            if type(leaf) is tuple:
                # grouped flat leaf: unpadded 1-D → repad → re-split into
                # the current row groups
                padded = self.flat.repad_unpadded(arr.reshape(-1))
                leaves.append(tuple(
                    self.flat.home_host_like(
                        padded[r0:r0 + rc].astype(g.dtype), g)
                    for (r0, rc), g in zip(self.flat.host_group_bounds,
                                           leaf)))
                continue
            if arr.ndim == 1 and leaf.shape == self.flat.flat_shape:
                # flat buffer saved unpadded (possibly different DP degree)
                arr = self.flat.repad_unpadded(arr)
            elif arr.shape != leaf.shape:
                # dp-geometry-dependent state (e.g. 1-bit Adam error
                # buffers) restored into a different DP degree: reset to
                # zeros — error feedback re-accumulates within a few steps
                logger.warning(
                    f"optimizer state {key}: checkpoint shape {arr.shape} != "
                    f"current {leaf.shape} (DP degree changed); resetting to "
                    f"zeros")
                leaves.append(self.flat.home_host_like(
                    np.zeros(leaf.shape, leaf.dtype), leaf))
                continue
            # every restored leaf is DONATED by the next step: re-home
            # through the coordinator so no numpy-owned memory is ever
            # donated (the two-live-engine / 8-device-dryrun glibc
            # corruption — see FlatParamCoordinator.home_host)
            leaves.append(self.flat.home_host_like(
                arr.astype(leaf.dtype), leaf))
        return jax.tree_util.tree_unflatten(treedef, leaves)
