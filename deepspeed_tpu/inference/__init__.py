"""Serving subsystem: paged-KV-cache inference with continuous batching,
priced and verified by the training-side toolchain (memory/comm ledgers,
program dumper, DSP6xx verifier, attribution doctor, EVENT telemetry).
"""

from .config import DeepSpeedInferenceConfig
from .engine import DECODE_PROGRAM, InferenceEngine, prefill_program_name
from .frontend import ServingFrontend, ServingOverloadError
from .kv_cache import (NULL_BLOCK, BlockAllocator, cache_bytes,
                       init_cache_buffers, init_kv_cache, kv_cache_bytes)
from .model import build_decode, build_prefill, reference_generate
from .observability import (SERVING_PHASE_KEYS,
                            SERVING_TRACE_SCHEMA_VERSION,
                            ServingObservability, mint_trace_id)
from .resilience import (ServingHealth, arm_serving_preemption,
                         serving_hang_quorum)
from .scheduler import (ContinuousBatchScheduler, Request, REASON_DEADLINE,
                        REASON_EOS, REASON_LENGTH)

__all__ = ["DeepSpeedInferenceConfig", "DECODE_PROGRAM", "InferenceEngine",
           "prefill_program_name", "ServingFrontend",
           "ServingOverloadError", "NULL_BLOCK", "BlockAllocator",
           "init_kv_cache", "kv_cache_bytes", "init_cache_buffers",
           "cache_bytes", "build_decode",
           "build_prefill", "reference_generate", "ServingHealth",
           "arm_serving_preemption", "serving_hang_quorum",
           "ContinuousBatchScheduler", "Request", "REASON_DEADLINE",
           "REASON_EOS", "REASON_LENGTH", "SERVING_PHASE_KEYS",
           "SERVING_TRACE_SCHEMA_VERSION", "ServingObservability",
           "mint_trace_id"]
