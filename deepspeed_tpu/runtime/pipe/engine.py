"""Pipeline-parallel engine: the schedule as one compiled SPMD program.

Re-design of ``deepspeed/runtime/pipe/engine.py`` (PipelineEngine ``:45``,
``train_batch`` ``:244``, ``_exec_schedule`` ``:1148``).  The reference
interprets an instruction stream per rank — python dispatch of
ForwardPass/SendActivation/... with NCCL broadcasts for p2p
(``p2p.py:31-55``) and a shape-metadata handshake (``:657-768``).  Under
XLA the entire training batch is **one jitted program**:

- ``lax.scan`` over the ``micro_batches + stages - 1`` fill+drain ticks
  (the InferenceSchedule tick count, reference ``schedule.py:135``);
- each tick, every stage applies its layer slice — ``lax.switch`` on
  ``lax.axis_index('pipe')`` selects the stage's computation;
- activations move stage→stage with a single ``ppermute`` ring shift
  (replacing SendActivation/RecvActivation and the meta handshake — shapes
  are static under SPMD, SURVEY §7 "hard parts");
- the backward schedule is not hand-written: differentiating the scanned
  forward yields the reversed drain-fill program (SendGrad/RecvGrad become
  the transpose of the forward ``ppermute``), and XLA's scheduler overlaps
  the collective-permutes with compute, which is the role of the
  reference's 1F1B interleave + CUDA streams;
- tied-weight gradient reduction (reference ``_exec_reduce_tied_grads``,
  ``pipe/engine.py:208-219``) is implicit: tied params appear once in the
  pytree, so autodiff sums their cotangents across stages;
- loss aggregation (reference ``_aggregate_total_loss`` ``:388-418``) is a
  ``psum`` over the ``pipe`` axis.

The instruction-stream schedules (``schedule.py``) remain the *description*
of this program — ``schedule_trace()`` emits them for tests/tracing.

Hybrid parallelism: the shard_map is manual over ``pipe`` only; ``data``
(DP/ZeRO) and ``model`` (TP) axes stay in GSPMD "auto" mode, so batch
sharding and the ZeRO flat-space machinery of the base engine compose
unchanged (PP×DP×TP, reference ``topology.py:246``).

Constraints of this execution model: stage-boundary activations may be any
pytree of arrays but must be uniform (same structure/shapes/dtypes) across
stage boundaries; a ``loss_fn`` is required when ``pipe > 1``.  With
``activation_checkpoint_interval`` set, each pipeline tick rematerializes,
so stored activations are only the in-flight boundary carries.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ...parallel.mesh import DATA_AXIS, PIPE_AXIS
from ...utils.logging import log_dist
from ..engine import DeepSpeedEngine
from .module import PipelineModule, split_batch
from .schedule import InferenceSchedule, TrainSchedule


class _PipelinedModel:
    """Adapter giving a :class:`PipelineModule` the engine's model contract
    (``init``/``apply``); ``apply`` is the full pipelined batch program."""

    def __init__(self, module: PipelineModule, engine: "PipelineEngine"):
        self.module = module
        self.engine = engine
        self._parts = None

    def init(self, rng):
        return self.module.init(rng)

    def partition_specs(self, mesh):
        # TP rules from the layers (3D hybrid: the `model` axis stays in
        # GSPMD auto mode under the pipe-manual shard_map)
        return self.module.partition_specs(mesh)

    # -- stage partitioning (trace-time, from param shapes) --
    def _ensure_parts(self, params):
        """Partition into ``stages × interleave`` LOGICAL stages; logical
        stage l lives on physical rank ``l % stages`` (Megatron's cyclic
        virtual-stage assignment)."""
        if self._parts is not None:
            return self._parts
        stages = self.engine.pipe_world_size
        if self.module.num_stages is not None:
            assert self.module.num_stages == stages, (
                f"PipelineModule(num_stages={self.module.num_stages}) but mesh "
                f"pipe axis is {stages}")
        counts = self.module.layer_param_counts(params)
        self._parts = self.module.partition_layers(
            stages * self.module.interleave, param_counts=counts)
        return self._parts

    def apply(self, params, batch, rng=None, train=False, **kw):
        module = self.module
        stages = self.engine.pipe_world_size
        assert module.loss_fn is not None, (
            "PipelineModule requires loss_fn to train under the engine")
        inputs, labels = split_batch(batch)
        assert labels is not None, (
            "pipeline batches must be (inputs, labels) tuples or "
            "{'inputs':..., 'labels':...} dicts")
        mb_count = jax.tree_util.tree_leaves(inputs)[0].shape[0]

        if stages == 1:
            # Degenerate pipeline = gradient accumulation: mean of the
            # micro-batch losses (reference DataParallelSchedule).
            def one(args):
                (mb_in, mb_lab), i = args
                r = jax.random.fold_in(rng, i) if rng is not None else None
                return module.sequential_apply(params, (mb_in, mb_lab),
                                               rng=r, train=train)

            losses = jax.lax.map(one, ((inputs, labels),
                                       jnp.arange(mb_count)))
            return jnp.mean(losses)

        parts = self._ensure_parts(params)
        v = module.interleave
        L = stages * v  # logical stages; logical l lives on rank l % stages
        if v > 1:
            assert mb_count % stages == 0, (
                f"interleave={v} needs micro_batches ({mb_count}) divisible "
                f"by stages ({stages}) — the schedule works in groups of "
                f"one micro-batch per rank")
            assert len(module.layer_specs) >= L, (
                f"interleave={v} with {stages} stages needs >= {L} layers "
                f"(got {len(module.layer_specs)}) — empty logical stages "
                "would silently forfeit the bubble reduction")

        # Boundary activation structure: chase shapes through the logical
        # stage slices and check they agree.  Boundaries may be any PYTREE
        # of arrays (uniform across stages) — multi-tensor carries like
        # (hidden, attention_bias) work; the reference's meta handshake
        # (pipe/engine.py:657-768) is this check, done at trace time.
        sample_in = jax.tree_util.tree_map(lambda a: a[0], inputs)
        btree = jax.eval_shape(
            lambda p, x: module.apply_range(p, 0, parts[1], x), params, sample_in)
        bstruct = jax.tree_util.tree_structure(btree)
        for s in range(1, L - 1):
            nxt = jax.eval_shape(
                lambda p, x: module.apply_range(p, parts[s], parts[s + 1], x),
                params, btree)
            same = (jax.tree_util.tree_structure(nxt) == bstruct and all(
                a.shape == b2.shape and a.dtype == b2.dtype
                for a, b2 in zip(jax.tree_util.tree_leaves(nxt),
                                 jax.tree_util.tree_leaves(btree))))
            assert same, (
                f"logical stage {s} boundary {nxt} != previous boundary "
                f"{btree}; pipeline stages must exchange one uniform "
                "activation pytree")
            btree = nxt

        def zeros_boundary():
            return jax.tree_util.tree_map(
                lambda sd: jnp.zeros(sd.shape, sd.dtype), btree)

        def cast_boundary(y):
            return jax.tree_util.tree_map(
                lambda a, sd: a.astype(sd.dtype), y, btree)

        def branch_fn(s):
            def chunk_fn(c):
                l = c * stages + s
                first, last = l == 0, l == L - 1

                def chunk(params, x_in, mb_inputs, mb_labels, valid, tick_rng):
                    x = mb_inputs if first else x_in
                    layer_kw = {"deterministic": not train}
                    if tick_rng is not None:
                        layer_kw["rng"] = tick_rng
                    # interval=0: the engine remats whole ticks (below);
                    # nesting apply_range's per-chunk remat inside would
                    # recompute the forward twice in backward
                    y = module.apply_range(params, parts[l], parts[l + 1], x,
                                           interval=0, **layer_kw)
                    if last:
                        loss = module.loss_fn(y, mb_labels)
                        loss = jnp.where(valid, loss.astype(jnp.float32), 0.0)
                        return zeros_boundary(), loss
                    return cast_boundary(y), jnp.asarray(0.0, jnp.float32)

                return chunk

            chunks = [chunk_fn(c) for c in range(v)]

            def branch(params, x_in, mb_inputs, mb_labels, valid, tick_rng, c):
                if v == 1:
                    return chunks[0](params, x_in, mb_inputs, mb_labels,
                                     valid, tick_rng)
                return jax.lax.switch(c, chunks, params, x_in, mb_inputs,
                                      mb_labels, valid, tick_rng)

            return branch

        branches = [branch_fn(s) for s in range(stages)]
        perm = [(i, (i + 1) % stages) for i in range(stages)]
        # Interleaved (v > 1): ticks are CHUNK-granularity.  Work index
        # w = t - rank; chunk c = (w//p) % v, micro = (w//(p·v))·p + w%p
        # (groups of one micro-batch per rank).  Every producer-consumer
        # pair is exactly one tick apart on the same ring, so one carry
        # per rank and one ppermute per tick serve all v virtual stages.
        # Executed ticks: v·mb + p − 1 chunk-ticks vs GPipe's (mb + p −1)·v
        # — the fill/drain bubble (which this compiled schedule EXECUTES,
        # masked) shrinks by ~v.
        ticks = v * mb_count + stages - 1

        # Per-tick rematerialization: differentiate-through-scan saves every
        # tick's layer-internal activations by default (O(ticks·layers)
        # live memory).  Checkpointing the tick body stores only the
        # boundary carries and recomputes stage internals in backward — the
        # memory profile of the reference's activation-checkpointed 1F1B
        # (stored state = in-flight boundary activations).  Enabled by the
        # module's activation_checkpoint_interval knob.
        per_tick_remat = bool(module.activation_checkpoint_interval)

        def per_pipe(params, inputs, labels, rng):
            s = jax.lax.axis_index(PIPE_AXIS)

            def tick_compute(params, x_state, mb_inputs, mb_labels, valid,
                             tick_rng, c):
                return jax.lax.switch(s, branches, params, x_state,
                                      mb_inputs, mb_labels, valid, tick_rng, c)

            if per_tick_remat:
                tick_compute = jax.checkpoint(tick_compute)

            def tick(carry, t):
                x_state, loss_sum = carry
                w = t - s  # this rank's work index this tick
                valid = jnp.logical_and(w >= 0, w < v * mb_count)
                wc = jnp.clip(w, 0, v * mb_count - 1)
                c = (wc // stages) % v
                micro = (wc // (stages * v)) * stages + (wc % stages)
                mb_inputs = jax.tree_util.tree_map(
                    lambda a: jax.lax.dynamic_index_in_dim(a, micro, 0,
                                                           keepdims=False),
                    inputs)
                mb_labels = jax.tree_util.tree_map(
                    lambda a: jax.lax.dynamic_index_in_dim(a, micro, 0,
                                                           keepdims=False),
                    labels)
                # per-(micro-batch, logical stage) dropout rng, like the
                # reference's per-buffer RNG state
                tick_rng = (jax.random.fold_in(
                    jax.random.fold_in(rng, micro), c * stages + s)
                            if rng is not None else None)
                y, loss = tick_compute(params, x_state, mb_inputs, mb_labels,
                                       valid, tick_rng, c)
                x_next = jax.tree_util.tree_map(
                    lambda a: jax.lax.ppermute(a, PIPE_AXIS, perm), y)
                return (x_next, loss_sum + jnp.reshape(loss, (1,))), None

            # the loss accumulator is a 1-element vector; [0] is taken
            # after the psum below
            (x_state, loss_sum), _ = jax.lax.scan(
                tick, (zeros_boundary(), jnp.zeros((1,), jnp.float32)),
                jnp.arange(ticks))
            # reference _aggregate_total_loss: last stage holds the sum;
            # broadcast down the pipe group == psum here (others hold 0)
            return jax.lax.psum(loss_sum, PIPE_AXIS)[0] / mb_count

        if rng is None:
            pipelined = shard_map(
                lambda p, i, l: per_pipe(p, i, l, None),
                mesh=self.engine.mesh,
                in_specs=(P(), P(), P()), out_specs=P(),
                axis_names={PIPE_AXIS}, check_vma=False)
            return pipelined(params, inputs, labels)
        pipelined = shard_map(
            per_pipe, mesh=self.engine.mesh,
            in_specs=(P(), P(), P(), P()), out_specs=P(),
            axis_names={PIPE_AXIS}, check_vma=False)
        return pipelined(params, inputs, labels, rng)


class PipelineEngine(DeepSpeedEngine):
    """Training engine for :class:`PipelineModule` models (reference
    ``pipe/engine.py:45``).  ``train_batch``/``eval_batch`` are the public
    loop API; ``forward/backward/step`` still work and see the whole global
    batch at once."""

    def __init__(self, args=None, model=None, optimizer=None,
                 model_parameters=None, training_data=None, lr_scheduler=None,
                 dist_init_required=None, collate_fn=None, config=None,
                 config_params=None, mesh=None):
        assert isinstance(model, PipelineModule), (
            "PipelineEngine requires a PipelineModule")
        self.pipe_module = model
        # the pipelined apply already averages over micro-batches, so the
        # base engine must not divide the loss by grad_acc again
        self._grad_divisor = 1.0
        adapter = _PipelinedModel(model, self)
        super().__init__(args=args, model=adapter, optimizer=optimizer,
                         model_parameters=model_parameters,
                         training_data=training_data, lr_scheduler=lr_scheduler,
                         dist_init_required=dist_init_required,
                         collate_fn=collate_fn, config=config,
                         config_params=config_params, mesh=mesh)
        shape = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        assert shape.get(PIPE_AXIS, 1) >= 1
        # json "pipeline" section (reference config.py:363-374) fills in
        # knobs the module constructor left at defaults — applied before the
        # first trace, so the compiled schedule sees them
        pipe_cfg = self._config.pipeline or {}
        ckpt_interval = pipe_cfg.get("activation_checkpoint_interval", 0)
        if ckpt_interval and not model.activation_checkpoint_interval:
            model.activation_checkpoint_interval = ckpt_interval
            log_dist(f"pipeline config: activation_checkpoint_interval="
                     f"{ckpt_interval}", ranks=[0])
        # None = key absent (distinct from any explicit value, so an
        # explicit "best" is honored rather than read as the unset sentinel)
        part = pipe_cfg.get("partition")
        if part is not None and model.partition_method == "parameters":
            # "best" is the config-level alias for parameter-balanced
            model.partition_method = "parameters" if part == "best" else part
            log_dist(f"pipeline config: partition={part}", ranks=[0])
        il = pipe_cfg.get("interleave")
        if il is not None and model.interleave == 1:
            model.interleave = max(int(il), 1)
            log_dist(f"pipeline config: interleave={il} (virtual stages)",
                     ranks=[0])
        elif il is not None and int(il) != model.interleave:
            # module constructor wins; say so instead of silently dropping
            # the JSON value
            log_dist(
                f"pipeline config: interleave={il} ignored — the "
                f"PipelineModule was constructed with "
                f"interleave={model.interleave}, which takes precedence",
                ranks=[0])
        self.micro_batches = self.gradient_accumulation_steps()
        # one pipelined forward/backward covers the whole global batch
        self.tput_timer.batch_size = self.train_batch_size()
        self.log_batch_step_id = 0
        log_dist(
            f"PipelineEngine: stages={self.pipe_world_size} "
            f"micro_batches={self.micro_batches} dp={self.dp_world_size}",
            ranks=[0])

    @property
    def pipe_world_size(self):
        shape = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        return shape.get(PIPE_AXIS, 1)

    def is_gradient_accumulation_boundary(self):
        # one pipelined forward covers all micro-batches
        return True

    def _stack_micro_batches(self, data_iter):
        """Pull ``micro_batches`` batches and stack them on a new leading
        axis (the reference streams them through LoadMicroBatch instead)."""
        micros = [next(data_iter) for _ in range(self.micro_batches)]
        return jax.tree_util.tree_map(lambda *xs: np.stack(xs), *micros)

    def _shard_batch(self, batch):
        """[micro, batch, ...] leaves: shard the *batch* dim over data."""
        sharding = NamedSharding(self.mesh, P(None, DATA_AXIS))

        def put(x):
            x = np.asarray(x)
            return jax.device_put(x, sharding)

        return jax.tree_util.tree_map(put, batch)

    def train_batch(self, data_iter=None):
        """One full training batch (reference ``pipe/engine.py:244-318``):
        schedule = fill+drain forward inside one program, autodiff backward,
        optimizer step."""
        if data_iter is None:
            assert self.training_dataloader is not None
            if not hasattr(self, "_train_iter"):
                from ..dataloader import RepeatingLoader
                self._train_iter = iter(RepeatingLoader(self.training_dataloader))
            data_iter = self._train_iter
        self.tput_timer.start()
        t_host0 = time.perf_counter()
        batch = self._stack_micro_batches(data_iter)
        loss = self.forward(batch)
        self.backward(loss)
        # backward() credited one micro-batch; this program ran all of them
        self.micro_steps += self.micro_batches - 1
        self.global_samples += (self.train_micro_batch_size_per_gpu()
                                * self.dp_world_size * (self.micro_batches - 1))
        # attribution driver bracket: stack/put + async dispatch are
        # host driver work; step()'s blocking scalar fetch is device
        # time and stays excluded (same split as the fused path)
        self._driver_latencies.record(time.perf_counter() - t_host0)
        self.step()
        self.tput_timer.stop()
        if self.telemetry.enabled:
            # same per-step telemetry surface as the fused train_batch
            # path (host-only bookkeeping on the already-run step): the
            # pipelined schedule's ppermute ring traffic lands in the
            # comm ledger via the fwd_bwd program it compiles through
            self.telemetry.counter("train/steps").inc()
            self.telemetry.counter("train/samples").inc(
                self.train_batch_size())
            self.telemetry.histogram("train/host_step_secs").observe(
                time.perf_counter() - t_host0)
            self.telemetry.poll_device_trace(self.global_steps)
        self.log_batch_step_id += 1
        return loss

    def eval_batch(self, data_iter):
        """Forward-only pipelined evaluation (reference ``:320-386``)."""
        if not isinstance(data_iter, dict) and hasattr(data_iter, "__next__"):
            batch = self._stack_micro_batches(data_iter)
        else:
            batch = jax.tree_util.tree_map(lambda x: np.asarray(x)[None], data_iter)
        batch = self._shard_batch(batch)
        with self.mesh:
            return self._eval_fn(self._forward_params(), batch,
                                 self._next_rng(), self._extra_kwargs())

    def schedule_trace(self, stage_id=0, kind="train", micro_batches=None):
        """Instruction stream describing the compiled program for one stage
        (reference's executable schedule, here exposed for tests/tracing)."""
        micro_batches = micro_batches or self.micro_batches
        cls = TrainSchedule if kind == "train" else InferenceSchedule
        sched = cls(micro_batches=micro_batches, stages=self.pipe_world_size,
                    stage_id=stage_id)
        return [list(step) for step in sched]
