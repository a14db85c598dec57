"""Pallas TPU kernels for the Maple dataflow: compiled through Mosaic on a
TPU, interpreted on the CPU for tests (``ops`` decides which); see each
kernel's module docstring for the hardware mapping."""

from repro.kernels.autotune import (SearchReport, auto_plan, fit_calibration,
                                    load_calibration, plan_cache_clear,
                                    plan_cache_stats, plan_search,
                                    plan_search_vjp, time_interleaved)
from repro.kernels.ops import (
    csr_to_ell,
    local_block_attention,
    maple_spgemm,
    maple_spmm,
    maple_spmspm,
    moe_expert_gemm,
)
from repro.kernels.partition import (PartitionedSpmmPlan,
                                     plan_partitioned_spmm,
                                     plan_partitioned_spmm_vjp)
from repro.kernels.reorder import (RowReorder, apply_reorder,
                                   plan_reordered_spmm, reorder_rows)
from repro.kernels.schedule import (ExecutionPlan, SpgemmPlan, SpmmPlan,
                                    SpmmTrainPlan, bsr_stats,
                                    pattern_fingerprint, plan_spgemm,
                                    plan_spmm, plan_spmm_vjp,
                                    spmm_knob_space)

__all__ = ["maple_spmm", "maple_spgemm", "maple_spmspm", "moe_expert_gemm",
           "csr_to_ell", "local_block_attention", "ExecutionPlan",
           "SpmmPlan", "SpgemmPlan", "SpmmTrainPlan", "PartitionedSpmmPlan",
           "bsr_stats", "plan_spmm", "plan_spgemm", "plan_spmm_vjp",
           "plan_partitioned_spmm", "plan_partitioned_spmm_vjp",
           "RowReorder", "reorder_rows", "apply_reorder",
           "plan_reordered_spmm",
           "pattern_fingerprint", "spmm_knob_space", "SearchReport",
           "auto_plan", "plan_search", "plan_search_vjp", "plan_cache_clear",
           "plan_cache_stats", "fit_calibration", "load_calibration",
           "time_interleaved"]
