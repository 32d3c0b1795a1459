from .attribution import (flops_cross_check, program_budget, reconcile,
                          step_budget, straggler_explanation)
from .comm import (CommLedger, collective_summary, fleet_skew,
                   parse_hlo_collectives, predicted_wire_bytes,
                   publish_rank_latency, read_fleet_latencies,
                   step_program_weights)
from .config import DeepSpeedFlopsProfilerConfig, DeepSpeedProfilingConfig
from .flops_profiler import (FlopsProfiler, count_fn_flops, get_model_profile)
from .memory import (HostBufferRegistry, MemoryLedger, device_memory_summary,
                     see_memory_usage)
from .overlap import analyze_hlo, parse_hlo_transfers, transfer_summary
from .sharding import analyze_sharding, entry_parameters
from .utilization import (PEAK_TFLOPS, chip_peak_tflops, chip_specs,
                          model_flops_utilization)

__all__ = ["CommLedger", "collective_summary", "parse_hlo_collectives",
           "predicted_wire_bytes", "publish_rank_latency",
           "read_fleet_latencies", "fleet_skew",
           "DeepSpeedFlopsProfilerConfig", "DeepSpeedProfilingConfig",
           "FlopsProfiler", "count_fn_flops", "get_model_profile",
           "MemoryLedger", "HostBufferRegistry",
           "device_memory_summary", "see_memory_usage", "PEAK_TFLOPS",
           "chip_peak_tflops", "chip_specs",
           "model_flops_utilization", "analyze_hlo",
           "parse_hlo_transfers", "transfer_summary",
           "analyze_sharding", "entry_parameters",
           "step_program_weights", "program_budget", "step_budget",
           "reconcile", "straggler_explanation", "flops_cross_check"]
