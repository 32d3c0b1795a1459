"""Config keys + defaults.

Mirrors the key/default tables of the reference ``deepspeed/runtime/constants.py``
and ``deepspeed/runtime/zero/constants.py`` so JSON configs written for the
reference parse unchanged.  TPU-specific additions are marked.
"""

#############################################
# Batch (reference runtime/constants.py)
#############################################
TRAIN_BATCH_SIZE = "train_batch_size"
TRAIN_BATCH_SIZE_DEFAULT = None

TRAIN_MICRO_BATCH_SIZE_PER_GPU = "train_micro_batch_size_per_gpu"
TRAIN_MICRO_BATCH_SIZE_PER_GPU_DEFAULT = None

GRADIENT_ACCUMULATION_STEPS = "gradient_accumulation_steps"
GRADIENT_ACCUMULATION_STEPS_DEFAULT = None

#############################################
# Optimizer / scheduler
#############################################
OPTIMIZER = "optimizer"
OPTIMIZER_TYPE_DEFAULT = None
OPTIMIZER_PARAMS = "params"
TYPE = "type"
LEGACY_FUSION = "legacy_fusion"
LEGACY_FUSION_DEFAULT = False

SCHEDULER = "scheduler"
SCHEDULER_TYPE_DEFAULT = None
SCHEDULER_PARAMS = "params"
MAX_GRAD_NORM = "max_grad_norm"

ADAM_OPTIMIZER = "adam"
LAMB_OPTIMIZER = "lamb"
ONEBIT_ADAM_OPTIMIZER = "onebitadam"
DEEPSPEED_OPTIMIZERS = [ADAM_OPTIMIZER, LAMB_OPTIMIZER, ONEBIT_ADAM_OPTIMIZER]

#############################################
# Precision
#############################################
FP32_ALLREDUCE = "fp32_allreduce"
FP32_ALLREDUCE_DEFAULT = False

PRESCALE_GRADIENTS = "prescale_gradients"
PRESCALE_GRADIENTS_DEFAULT = False

GRADIENT_PREDIVIDE_FACTOR = "gradient_predivide_factor"
GRADIENT_PREDIVIDE_FACTOR_DEFAULT = 1.0

SPARSE_GRADIENTS = "sparse_gradients"
SPARSE_GRADIENTS_DEFAULT = False

FP16 = "fp16"
FP16_ENABLED = "enabled"
FP16_ENABLED_DEFAULT = False
FP16_LOSS_SCALE = "loss_scale"
FP16_LOSS_SCALE_DEFAULT = 0
FP16_INITIAL_SCALE_POWER = "initial_scale_power"
FP16_INITIAL_SCALE_POWER_DEFAULT = 32
FP16_LOSS_SCALE_WINDOW = "loss_scale_window"
FP16_LOSS_SCALE_WINDOW_DEFAULT = 1000
FP16_HYSTERESIS = "hysteresis"
FP16_HYSTERESIS_DEFAULT = 2
FP16_MIN_LOSS_SCALE = "min_loss_scale"
FP16_MIN_LOSS_SCALE_DEFAULT = 1

# TPU addition: bf16 is the native mixed-precision mode (no loss scaling).
BF16 = "bf16"
BF16_ENABLED = "enabled"
BF16_ENABLED_DEFAULT = False

AMP = "amp"
AMP_ENABLED = "enabled"
AMP_ENABLED_DEFAULT = False

GRADIENT_CLIPPING = "gradient_clipping"
GRADIENT_CLIPPING_DEFAULT = 0.0

#############################################
# Communication / DP
#############################################
# dslint: disable=DSC401 -- reference-API alias of FP32_ALLREDUCE (same JSON key; parsing happens under that name)
ALLREDUCE_ALWAYS_FP32 = "fp32_allreduce"
DISABLE_ALLGATHER = "disable_allgather"
DISABLE_ALLGATHER_DEFAULT = False

ALLGATHER_SIZE = "allgather_size"
ALLGATHER_SIZE_DEFAULT = 500000000

#############################################
# Logging / profiling
#############################################
STEPS_PER_PRINT = "steps_per_print"
STEPS_PER_PRINT_DEFAULT = 10

WALL_CLOCK_BREAKDOWN = "wall_clock_breakdown"
WALL_CLOCK_BREAKDOWN_DEFAULT = False

MEMORY_BREAKDOWN = "memory_breakdown"
MEMORY_BREAKDOWN_DEFAULT = False

DUMP_STATE = "dump_state"
DUMP_STATE_DEFAULT = False

TENSORBOARD = "tensorboard"
TENSORBOARD_ENABLED = "enabled"
TENSORBOARD_ENABLED_DEFAULT = False
TENSORBOARD_OUTPUT_PATH = "output_path"
TENSORBOARD_OUTPUT_PATH_DEFAULT = ""
TENSORBOARD_JOB_NAME = "job_name"
TENSORBOARD_JOB_NAME_DEFAULT = "DeepSpeedJobName"

#############################################
# ZeRO (reference runtime/zero/constants.py)
#############################################
ZERO_OPTIMIZATION = "zero_optimization"
ZERO_OPTIMIZATION_DISABLED = 0
ZERO_OPTIMIZATION_OPTIMIZER_STATES = 1
ZERO_OPTIMIZATION_GRADIENTS = 2
ZERO_OPTIMIZATION_WEIGHTS = 3
# The reference caps at stage 2 (zero/constants.py:33); the TPU rebuild
# implements stage 3 as well (sharded parameters are natural under SPMD).
MAX_STAGE_ZERO_OPTIMIZATION = ZERO_OPTIMIZATION_WEIGHTS

ZERO_STAGE = "stage"
ZERO_STAGE_DEFAULT = ZERO_OPTIMIZATION_DISABLED
ZERO_ALLOW_UNTESTED_OPTIMIZER = "zero_allow_untested_optimizer"
ZERO_ALLOW_UNTESTED_OPTIMIZER_DEFAULT = False

ZERO_REDUCE_SCATTER = "reduce_scatter"
ZERO_REDUCE_SCATTER_DEFAULT = True
ZERO_REDUCE_BUCKET_SIZE = "reduce_bucket_size"
ZERO_REDUCE_BUCKET_SIZE_DEFAULT = 500000000
ZERO_ALLGATHER_BUCKET_SIZE = "allgather_bucket_size"
ZERO_ALLGATHER_BUCKET_SIZE_DEFAULT = 500000000
# Bucketed gradient-collective overlap (round 14): split the ZeRO-2
# data-parallel gradient exchange into reduce_bucket_size-bounded,
# leaf-aligned buckets issued as explicit per-bucket psum_scatters in
# backward-production order (and the master all-gather into
# allgather_bucket_size groups), so the collectives overlap backward /
# update compute instead of landing as one fused end-of-backward
# exchange.  "auto" engages whenever supported (stage-2 pure-dp mesh,
# flat Adam/AdamW, no cpu_offload/sparse_gradients); true raises on an
# unsupported config; false keeps the GSPMD fused exchange — the
# measured serialized control.
ZERO_OVERLAP_COMM = "overlap_comm"
ZERO_OVERLAP_COMM_DEFAULT = "auto"
ZERO_CONTIGUOUS_GRADIENTS = "contiguous_gradients"
ZERO_CONTIGUOUS_GRADIENTS_DEFAULT = False
ZERO_CPU_OFFLOAD = "cpu_offload"
ZERO_CPU_OFFLOAD_DEFAULT = False
# Offloaded master/optimizer state streams through the device in chunks of
# at most this many megabytes of fp32 rows per buffer (TPU-native analog of
# the reference's grad/param bucket sizes for ZeRO-Offload, stage2.py:326):
# bounds peak HBM during the update to ~one chunk of (p, m, v) instead of
# three full buffers.  0 disables chunking.
ZERO_OFFLOAD_CHUNK_MB = "offload_chunk_mb"
ZERO_OFFLOAD_CHUNK_MB_DEFAULT = 512
# Keep the flat fp32 gradient buffer in pinned host memory too (reference
# ZeRO-Offload moves averaged gradients to CPU as the backward produces
# them, stage2.py:622-668): the compiled step writes gradient rows out
# chunk-by-chunk as the backward frees them and the streamed update reads
# them back per chunk, so device HBM never holds the full 4 bytes/param
# gradient buffer — the last per-param device cost beyond the bf16 params.
ZERO_OFFLOAD_GRADIENTS = "offload_gradients"
ZERO_OFFLOAD_GRADIENTS_DEFAULT = False
# Uniform-chunk (O(1)-compile) streamed update: pad the offloaded row
# layout so every chunk has one shape and drive the chunk sequence with
# lax.scan — compile cost stops scaling with chunk count (the round-5
# capacity ceiling was >30-min compiles past ~1.5B params, not memory).
# "auto" engages past UNIFORM_MIN_CHUNKS (zero/stream.py) chunks of
# state; true forces it at any size; false keeps the unrolled
# round-robin form everywhere.
ZERO_OFFLOAD_UNIFORM_CHUNKS = "offload_uniform_chunks"
ZERO_OFFLOAD_UNIFORM_CHUNKS_DEFAULT = "auto"
# Max megabytes per pinned-host row-group buffer.  Default 1792 MB gives
# mid-size states >= 2 groups for the round-robin transfer/compute
# overlap (measured -5% step time at gpt2-large); very large states can
# raise it toward the ~3.5 GB toolchain bound to halve the buffer count
# (measured: the remote AOT compile helper crashes on the many-buffer
# gpt2-xl+offload_gradients program at 1792 but compiles at 3584).
ZERO_OFFLOAD_GROUP_MB = "offload_group_mb"
ZERO_OFFLOAD_GROUP_MB_DEFAULT = 1792
ZERO_ELASTIC_CHECKPOINT = "elastic_checkpoint"
ZERO_ELASTIC_CHECKPOINT_DEFAULT = True
# Overlapped chunk streaming (round 12): issue the streamed update as a
# double-buffered host<->device pipeline — prefetch chunk k+1's host
# state while chunk k's device update runs, and overlap chunk k's
# write-back with the next fetch — instead of the serialized
# load->update->write-back chain.  Same per-chunk math in the same
# order (bit-identical updates, CI parity-tested); only the ISSUE order
# of the transfers changes, so the wire hides behind update compute.
# "auto" (default) overlaps whenever the update streams; false keeps
# the serialized schedule (the measured-receipts control); true forces
# the config intent and raises if the update cannot stream at all.
ZERO_OFFLOAD_OVERLAP = "offload_overlap"
ZERO_OFFLOAD_OVERLAP_DEFAULT = "auto"
# Chunks in flight in the overlapped pipeline: depth d keeps d-1
# prefetched chunks resident on device while one updates (device peak
# grows by (d-1) chunk states).  2 = classic double buffering; 1 is
# the serialized schedule (what offload_overlap: false selects).
ZERO_OFFLOAD_PREFETCH_DEPTH = "offload_prefetch_depth"
ZERO_OFFLOAD_PREFETCH_DEPTH_DEFAULT = 2
# Reduced-precision host optimizer state (zero/qstate.py): store the
# pinned-host (p, m, v) buffers in bf16/fp16 and upcast to fp32 on
# device inside the streamed update — the offload step is wire-bound
# (PERF.md "ZeRO-Offload wire bytes"), so halving the bytes on the
# PCIe wire is the step-time lever streaming overlap cannot reach.
# Sub-block of zero_optimization; also accepts the shorthand string
# "bf16"/"fp16" meaning master+momentum+variance all at that dtype.
ZERO_OFFLOAD_STATE_DTYPE = "offload_state_dtype"
# storage dtype of the flat fp32 master ("fp32" | "bf16"; fp16's 5-bit
# exponent cannot carry master weights safely and is rejected)
ZERO_OFFLOAD_STATE_DTYPE_MASTER = "master"
ZERO_OFFLOAD_STATE_DTYPE_MASTER_DEFAULT = "fp32"
# storage dtype of Adam's first moment m ("fp32" | "bf16" | "fp16")
ZERO_OFFLOAD_STATE_DTYPE_MOMENTUM = "momentum"
ZERO_OFFLOAD_STATE_DTYPE_MOMENTUM_DEFAULT = "fp32"
# storage dtype of Adam's second moment v ("fp32" | "bf16" | "fp16")
ZERO_OFFLOAD_STATE_DTYPE_VARIANCE = "variance"
ZERO_OFFLOAD_STATE_DTYPE_VARIANCE_DEFAULT = "fp32"
# write-back mechanism: false (default) -> the `rounding` mode below;
# true -> a persistent error-feedback residual buffer per reduced
# buffer (deterministic, rides the chunk stream AND the checkpoint, at
# the cost of its own wire bytes)
ZERO_OFFLOAD_STATE_DTYPE_ERROR_FEEDBACK = "error_feedback"
ZERO_OFFLOAD_STATE_DTYPE_ERROR_FEEDBACK_DEFAULT = False
# "stochastic" (default: unbiased SR downcast — sub-ulp updates survive
# in expectation at zero extra wire bytes) | "nearest" (plain downcast;
# drifts by construction — kept as the measurable control)
ZERO_OFFLOAD_STATE_DTYPE_ROUNDING = "rounding"
ZERO_OFFLOAD_STATE_DTYPE_ROUNDING_DEFAULT = "stochastic"
# seed of the stochastic-rounding bit stream (folded with the optimizer
# step and chunk index, so directions decorrelate across steps/chunks)
ZERO_OFFLOAD_STATE_DTYPE_SEED = "seed"
ZERO_OFFLOAD_STATE_DTYPE_SEED_DEFAULT = 0

#############################################
# Pipeline (reference runtime/config.py:363-374)
#############################################
PIPELINE = "pipeline"
PIPELINE_STAGES = "stages"
PIPELINE_STAGES_DEFAULT = None
PIPELINE_PARTITION = "partition"
PIPELINE_PARTITION_DEFAULT = "best"
PIPELINE_SEED_LAYERS = "seed_layers"
PIPELINE_SEED_LAYERS_DEFAULT = False
PIPELINE_ACTIVATION_CHECKPOINT_INTERVAL = "activation_checkpoint_interval"
PIPELINE_ACTIVATION_CHECKPOINT_INTERVAL_DEFAULT = 0

#############################################
# Gradient noise scale / PLD
#############################################
PROGRESSIVE_LAYER_DROP = "progressive_layer_drop"
PLD_ENABLED = "enabled"
PLD_ENABLED_DEFAULT = False
PLD_THETA = "theta"
PLD_THETA_DEFAULT = 1.0
PLD_GAMMA = "gamma"
PLD_GAMMA_DEFAULT = 0.001

#############################################
# TPU mesh (new; no reference analog — replaces launcher world-size math)
#############################################
MESH = "mesh"
MESH_DATA = "data"
MESH_MODEL = "model"
MESH_PIPE = "pipe"
MESH_SEQ = "seq"

#############################################
# Sparse attention (reference runtime/config.py:192-360)
#############################################
SPARSE_ATTENTION = "sparse_attention"
SPARSE_MODE = "mode"
SPARSE_MODE_DEFAULT = "fixed"
SPARSE_DENSE_MODE = "dense"
SPARSE_FIXED_MODE = "fixed"
SPARSE_VARIABLE_MODE = "variable"
SPARSE_BIGBIRD_MODE = "bigbird"
SPARSE_BSLONGFORMER_MODE = "bslongformer"
SPARSE_BLOCK = "block"
SPARSE_BLOCK_DEFAULT = 16
SPARSE_DIFFERENT_LAYOUT_PER_HEAD = "different_layout_per_head"
SPARSE_DIFFERENT_LAYOUT_PER_HEAD_DEFAULT = False
SPARSE_NUM_LOCAL_BLOCKS = "num_local_blocks"
SPARSE_NUM_LOCAL_BLOCKS_DEFAULT = 4
SPARSE_NUM_GLOBAL_BLOCKS = "num_global_blocks"
SPARSE_NUM_GLOBAL_BLOCKS_DEFAULT = 1
SPARSE_ATTENTION_TYPE = "attention"
SPARSE_ATTENTION_TYPE_DEFAULT = "bidirectional"
SPARSE_HORIZONTAL_GLOBAL_ATTENTION = "horizontal_global_attention"
SPARSE_HORIZONTAL_GLOBAL_ATTENTION_DEFAULT = False
SPARSE_NUM_DIFFERENT_GLOBAL_PATTERNS = "num_different_global_patterns"
SPARSE_NUM_DIFFERENT_GLOBAL_PATTERNS_DEFAULT = 1
SPARSE_NUM_RANDOM_BLOCKS = "num_random_blocks"
SPARSE_NUM_RANDOM_BLOCKS_DEFAULT = 0
SPARSE_LOCAL_WINDOW_BLOCKS = "local_window_blocks"
SPARSE_LOCAL_WINDOW_BLOCKS_DEFAULT = [4]
SPARSE_GLOBAL_BLOCK_INDICES = "global_block_indices"
SPARSE_GLOBAL_BLOCK_INDICES_DEFAULT = [0]
SPARSE_GLOBAL_BLOCK_END_INDICES = "global_block_end_indices"
SPARSE_GLOBAL_BLOCK_END_INDICES_DEFAULT = None
SPARSE_NUM_SLIDING_WINDOW_BLOCKS = "num_sliding_window_blocks"
SPARSE_NUM_SLIDING_WINDOW_BLOCKS_DEFAULT = 3

#############################################
# Checkpoint subsystem (deepspeed_tpu/checkpoint; new — the reference
# saves synchronously inline in the engine, SURVEY §3.5)
#############################################
CHECKPOINT = "checkpoint"
# hand the host-side snapshot to a background writer thread so
# train_batch resumes immediately; commits stay atomic either way
CHECKPOINT_ASYNC_SAVE = "async_save"
CHECKPOINT_ASYNC_SAVE_DEFAULT = True
# retention: keep the newest N committed checkpoints (0 = keep all) ...
CHECKPOINT_KEEP_LAST_N = "keep_last_n"
CHECKPOINT_KEEP_LAST_N_DEFAULT = 0
# ... plus every checkpoint whose step is a multiple of this (0 = none)
CHECKPOINT_KEEP_EVERY_N_STEPS = "keep_every_n_steps"
CHECKPOINT_KEEP_EVERY_N_STEPS_DEFAULT = 0
# re-checksum payload files against the manifest before restoring
CHECKPOINT_VERIFY_ON_LOAD = "verify_on_load"
CHECKPOINT_VERIFY_ON_LOAD_DEFAULT = True
# retries (beyond the first attempt) for a failed commit, with
# exponential backoff starting at retry_backoff_secs
CHECKPOINT_SAVE_RETRIES = "save_retries"
CHECKPOINT_SAVE_RETRIES_DEFAULT = 2
CHECKPOINT_RETRY_BACKOFF_SECS = "retry_backoff_secs"
CHECKPOINT_RETRY_BACKOFF_SECS_DEFAULT = 0.5
# drain one final synchronous save on SIGTERM (TPU preemption notice)
CHECKPOINT_SAVE_ON_PREEMPTION = "save_on_preemption"
CHECKPOINT_SAVE_ON_PREEMPTION_DEFAULT = False

#############################################
# Resilience subsystem (deepspeed_tpu/resilience; new — the reference's
# only runtime failure handling is fp16 overflow skip-and-rescale)
#############################################
RESILIENCE = "resilience"
RESILIENCE_ENABLED = "enabled"
RESILIENCE_ENABLED_DEFAULT = False
# what to do about anomalous steps beyond the always-on in-jit skip of
# non-finite updates: skip | rescale | rollback | abort
RESILIENCE_POLICY = "policy"
RESILIENCE_POLICY_DEFAULT = "skip"
# rolling window (in steps) for the loss-spike z-score; 0 disables
# spike detection (non-finite detection stays on)
RESILIENCE_SPIKE_WINDOW = "spike_window"
RESILIENCE_SPIKE_WINDOW_DEFAULT = 64
RESILIENCE_SPIKE_ZSCORE = "spike_zscore"
RESILIENCE_SPIKE_ZSCORE_DEFAULT = 6.0
# consecutive anomalous steps before rollback/abort policies escalate
RESILIENCE_DIVERGENCE_PATIENCE = "divergence_patience"
RESILIENCE_DIVERGENCE_PATIENCE_DEFAULT = 3
# rollback budget per run; exhausting it aborts with the poison code
RESILIENCE_MAX_ROLLBACKS = "max_rollbacks"
RESILIENCE_MAX_ROLLBACKS_DEFAULT = 2
# re-diverging within this many steps of the restored step = thrashing
RESILIENCE_ROLLBACK_COOLDOWN_STEPS = "rollback_cooldown_steps"
RESILIENCE_ROLLBACK_COOLDOWN_STEPS_DEFAULT = 0
# step watchdog: heartbeat stall (seconds) before the all-thread stack
# dump + respawnable exit; 0 disables the watchdog
RESILIENCE_HANG_TIMEOUT_SECS = "hang_timeout_secs"
RESILIENCE_HANG_TIMEOUT_SECS_DEFAULT = 0.0
# consecutive overflows with the fp16 loss scale pinned at min_scale
# before the guard declares the scaler stuck (loud error + anomaly event)
RESILIENCE_FLOOR_SCALE_PATIENCE = "floor_scale_patience"
RESILIENCE_FLOOR_SCALE_PATIENCE_DEFAULT = 8
# where rollback + auto_resume look for the latest committed checkpoint;
# default: the last directory this engine saved to or loaded from
RESILIENCE_CHECKPOINT_DIR = "checkpoint_dir"
RESILIENCE_CHECKPOINT_DIR_DEFAULT = None
# straggler detection: a rank whose p50 step latency exceeds this
# multiple of the fleet median (per-rank latency exchange, sampled at
# the steps_per_print cadence) raises a "straggler" anomaly event.
# 0 disables; needs telemetry (the run dir is the exchange medium)
RESILIENCE_STRAGGLER_FACTOR = "straggler_factor"
RESILIENCE_STRAGGLER_FACTOR_DEFAULT = 0.0
# fleet integrity plane (resilience/integrity.py): per-rank state
# fingerprints (a cheap in-jit checksum over the flat master +
# optimizer state, riding the existing batched steps_per_print fetch)
# cross-checked by majority vote over run-dir artifacts — an SDC/desync
# suspect is named, reported to the supervisor, and evicted on resize.
# Needs telemetry (the run dir is the exchange medium)
RESILIENCE_INTEGRITY = "integrity"
RESILIENCE_INTEGRITY_DEFAULT = False
# fingerprint history steps each rank publishes (voting scans the
# window, so ranks whose publishes lag the fleet head are still judged)
RESILIENCE_INTEGRITY_WINDOW = "integrity_window"
RESILIENCE_INTEGRITY_WINDOW_DEFAULT = 8
# evict: verdict file + FleetIntegrityError (exit 87, the supervisor
# resizes around the suspect); warn: telemetry events only (use on
# meshes that shard state across processes, where per-process
# fingerprints legitimately differ)
RESILIENCE_INTEGRITY_ACTION = "integrity_action"
RESILIENCE_INTEGRITY_ACTION_DEFAULT = "evict"
# fleet heartbeat + hang quorum: a peer whose step-entry beat lags the
# fleet head and goes stale by this many seconds is the hang suspect
# (healthy ranks exit with ONE respawnable eviction instead of N local
# watchdog timeouts).  0 disables the heartbeat thread
RESILIENCE_INTEGRITY_PEER_TIMEOUT_SECS = "integrity_peer_timeout_secs"
RESILIENCE_INTEGRITY_PEER_TIMEOUT_SECS_DEFAULT = 0.0

#############################################
# Telemetry subsystem (deepspeed_tpu/telemetry; new — the reference's
# observability is inline tensorboard scalars + throughput log lines)
#############################################
TELEMETRY = "telemetry"
TELEMETRY_ENABLED = "enabled"
TELEMETRY_ENABLED_DEFAULT = False
# where event streams / trace files / metric snapshots land; the report
# CLI reads this directory.  Empty -> "runs/telemetry"
TELEMETRY_RUN_DIR = "run_dir"
TELEMETRY_RUN_DIR_DEFAULT = ""
# structured JSONL event stream (events-rank<k>.jsonl)
TELEMETRY_EVENTS = "events"
TELEMETRY_EVENTS_DEFAULT = True
# Chrome-trace host-phase spans (trace-rank<k>.json, Perfetto-loadable)
TELEMETRY_TRACE = "trace"
TELEMETRY_TRACE_DEFAULT = False
# span cap per trace file: past it new spans are dropped (loudly)
TELEMETRY_TRACE_MAX_EVENTS = "trace_max_events"
TELEMETRY_TRACE_MAX_EVENTS_DEFAULT = 200000
# on-demand jax.profiler device traces: touching <run_dir>/
# device_trace.trigger starts one, auto-stopped after this many seconds
TELEMETRY_DEVICE_TRACE_SECS = "device_trace_secs"
TELEMETRY_DEVICE_TRACE_SECS_DEFAULT = 10.0
# override the trigger-file path (empty -> <run_dir>/device_trace.trigger)
TELEMETRY_DEVICE_TRACE_TRIGGER = "device_trace_trigger"
TELEMETRY_DEVICE_TRACE_TRIGGER_DEFAULT = ""

#############################################
# Profiling subsystem (deepspeed_tpu/profiling; the "flops_profiler"
# block keeps its reference-parity shape in profiling/config.py — this
# block holds the NEW memory-observability knobs)
#############################################
PROFILING = "profiling"
# compiled-program HBM ledger (profiling/memory.MemoryLedger): records
# each engine program's memory_analysis() as telemetry events/gauges at
# compile time.  "auto" follows telemetry.enabled; true forces it on
# even without telemetry (entries still queryable via
# engine.memory_ledger, e.g. for bench receipts); false disables
PROFILING_MEMORY_LEDGER = "memory_ledger"
PROFILING_MEMORY_LEDGER_DEFAULT = "auto"
# live HBM watermark gauges/events (bytes_in_use/peak summed over local
# devices + the host pinned-buffer registry), sampled ONLY at the
# existing batched steps_per_print fetch — zero new per-step syncs.
# "auto" follows telemetry.enabled
PROFILING_MEMORY_WATERMARKS = "memory_watermarks"
PROFILING_MEMORY_WATERMARKS_DEFAULT = "auto"
# compiled-program collective ledger (profiling/comm.CommLedger):
# walks each program's optimized HLO for collectives at compile time
# and records count/payload/replica-group/predicted-wire-bytes as
# telemetry events/gauges.  "auto" follows telemetry.enabled; true
# forces it on even without telemetry (entries still queryable via
# engine.comm_ledger, e.g. for bench/multichip receipts); false
# disables
PROFILING_COMM_LEDGER = "comm_ledger"
PROFILING_COMM_LEDGER_DEFAULT = "auto"
# per-program verification artifacts (profiling/verify.ProgramDumper):
# each compiled engine program's optimized HLO + a donation/mesh/comm
# sidecar land under <telemetry run_dir>/programs/ at compile time
# (rank 0 only), the input of the offline DSP6xx verifier
# `python -m deepspeed_tpu.tools.dslint --programs <run_dir>`.  "auto"
# follows the comm ledger (itself following telemetry.enabled); true
# forces the dump whenever a run dir exists; false disables
PROFILING_PROGRAM_DUMP = "program_dump"
PROFILING_PROGRAM_DUMP_DEFAULT = "auto"

#############################################
# Compilation subsystem (deepspeed_tpu/runtime/compilation; new — the
# reference has no compile-time story: CUDA kernels JIT per-op.  Under
# XLA whole-program compiles are minutes-to-tens-of-minutes at offload
# scale, so warm-starting them is a first-class subsystem.)
#############################################
COMPILATION = "compilation"
# persistent XLA compile cache (runtime/compilation/cache.py has the one
# rule for where it lives); false leaves jax's cache settings untouched
COMPILATION_CACHE = "cache"
COMPILATION_CACHE_DEFAULT = True
# where compiled executables persist when JAX_COMPILATION_CACHE_DIR is
# not set; empty -> <checkout>/.jax_cache.  The path is part of every
# cache key, so fresh processes (bench reruns, --max-restarts respawns,
# auto-resume restarts) hit only when they resolve the same directory.
COMPILATION_CACHE_DIR = "cache_dir"
COMPILATION_CACHE_DIR_DEFAULT = ""
# skip caching executables smaller than this (bytes): tiny programs
# cost more in cache I/O than they save
COMPILATION_MIN_ENTRY_SIZE_BYTES = "min_entry_size_bytes"
COMPILATION_MIN_ENTRY_SIZE_BYTES_DEFAULT = 0
# skip caching programs that compiled faster than this (seconds); 0
# caches everything — warm-start init wants even the small engine
# programs back
COMPILATION_MIN_COMPILE_SECS = "min_compile_secs"
COMPILATION_MIN_COMPILE_SECS_DEFAULT = 0.0

#############################################
# Ring / context parallel attention (TPU addition, SURVEY §5.7)
#############################################
RING_ATTENTION = "ring_attention"
RING_ATTENTION_ENABLED = "enabled"
RING_ATTENTION_ENABLED_DEFAULT = False

#############################################
# Inference / serving (deepspeed_tpu/inference; new — the reference
# v0.3.11 predates its inference engine entirely.  Orca-style
# continuous batching over a vLLM-style paged KV cache, adapted to
# XLA's static-shape world: every knob here is a SHAPE, so the engine
# compiles exactly len(prefill_buckets) + 1 programs and never
# retraces mid-serve.)
#############################################
INFERENCE = "inference"
# tokens per KV-cache block (the paged-allocation granularity; the
# prefill buckets and max_seq_len must be multiples of it)
INFERENCE_KV_BLOCK_SIZE = "kv_block_size"
INFERENCE_KV_BLOCK_SIZE_DEFAULT = 16
# total preallocated KV blocks per layer (the device-memory budget:
# 2 * layers * kv_blocks * kv_block_size * hidden * dtype bytes)
INFERENCE_KV_BLOCKS = "kv_blocks"
INFERENCE_KV_BLOCKS_DEFAULT = 256
# decode batch width: the FIXED slot count of the decode program
# (continuous batching recycles slots per iteration; the shape never
# changes, so the decode program compiles once)
INFERENCE_MAX_BATCH_SLOTS = "max_batch_slots"
INFERENCE_MAX_BATCH_SLOTS_DEFAULT = 4
# longest context (prompt + generated) a sequence may reach; bounds the
# per-slot block-table width
INFERENCE_MAX_SEQ_LEN = "max_seq_len"
INFERENCE_MAX_SEQ_LEN_DEFAULT = 64
# padded prefill lengths, ascending: each prompt compiles against the
# smallest bucket that fits, so prefill retraces are bounded by
# len(buckets) — the dslint DSR3xx bucketed-shape discipline
INFERENCE_PREFILL_BUCKETS = "prefill_buckets"
INFERENCE_PREFILL_BUCKETS_DEFAULT = (16, 32, 64)
# admission budget: a request is admitted only while the sum of
# (context + remaining generation) tokens over active slots stays
# under this — the Orca iteration-level admission knob
INFERENCE_TOKEN_BUDGET = "token_budget"
INFERENCE_TOKEN_BUDGET_DEFAULT = 2048
# per-request generation cap when the request does not set one
INFERENCE_MAX_NEW_TOKENS = "max_new_tokens"
INFERENCE_MAX_NEW_TOKENS_DEFAULT = 16
# stop token: a slot emitting it is finished and recycled mid-batch
# (-1 disables — fixed-length generation)
INFERENCE_EOS_TOKEN_ID = "eos_token_id"
INFERENCE_EOS_TOKEN_ID_DEFAULT = -1
# serve-time weight dtype: "bfloat16" casts every floating-point leaf
# at ingestion (module_inject surgery included); "float32" keeps the
# checkpoint dtype (the CPU-parity setting)
INFERENCE_WEIGHTS_DTYPE = "weights_dtype"
INFERENCE_WEIGHTS_DTYPE_DEFAULT = "float32"
# per-request wall-clock deadline in milliseconds: a request still
# queued or decoding when it expires is finished with
# reason="deadline" and its result carries the partial tokens; its
# slot/blocks recycle mid-batch.  0 disables (no deadline).
INFERENCE_REQUEST_DEADLINE_MS = "request_deadline_ms"
INFERENCE_REQUEST_DEADLINE_MS_DEFAULT = 0
# front-end admission bound: a submit() arriving while this many
# requests are already queued (across the replica fleet) is SHED with
# a typed overload error instead of queueing unboundedly.  0 disables
# (unbounded queue — the single-engine default).
INFERENCE_MAX_QUEUE_DEPTH = "max_queue_depth"
INFERENCE_MAX_QUEUE_DEPTH_DEFAULT = 0
# graceful degradation threshold: at or past this queue depth the
# front-end caps each new request's max_new_tokens at
# degraded_max_new_tokens, trading answer length for admission rate
# before shedding starts.  0 disables.
INFERENCE_DEGRADE_QUEUE_DEPTH = "degrade_queue_depth"
INFERENCE_DEGRADE_QUEUE_DEPTH_DEFAULT = 0
# the degraded generation cap applied past degrade_queue_depth
INFERENCE_DEGRADED_MAX_NEW_TOKENS = "degraded_max_new_tokens"
INFERENCE_DEGRADED_MAX_NEW_TOKENS_DEFAULT = 4
# "slo": {"ttft_ms": ..., "per_token_ms": ...} — the serving SLO
# targets the observability plane accounts goodput against (tokens from
# requests meeting the target vs raw throughput).  0 disables a leg;
# the SLO never changes scheduling, it only changes what gets counted.
INFERENCE_SLO = "slo"
INFERENCE_SLO_TTFT_MS = "ttft_ms"
INFERENCE_SLO_TTFT_MS_DEFAULT = 0
INFERENCE_SLO_PER_TOKEN_MS = "per_token_ms"
INFERENCE_SLO_PER_TOKEN_MS_DEFAULT = 0

#############################################
# Config validation (dslint schema; new — reference config.py:432 only
# checked a handful of keys by hand)
#############################################
# "strict_config": true turns unknown-key warnings (misspelled keys that
# dict.get would silently default) into hard DeepSpeedConfigError
STRICT_CONFIG = "strict_config"
STRICT_CONFIG_DEFAULT = False

ROUTE_PREFIX = "deepspeed"
