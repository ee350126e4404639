"""MG-WFBP core: the paper's contribution as a composable JAX module.

Public surface:
  comm_model  — α–β all-reduce cost models (paper Table II) + TPU ICI presets
  cost_model  — per-layer backward-time model (paper Eq. 18) + hardware presets
  timeline    — WFBP timeline evaluation (paper Eqs. 6–8, 19–21)
  schedule    — Algorithm 1 (MG-WFBP), WFBP/SyncEASGD/fixed-bucket baselines,
                exhaustive exact optimum
  bucketing   — param-pytree <-> schedule-bucket mapping (leaf + stacked
                units) + the per-group wire plan and flat arena layouts
  sync        — the bucketed reducer: one all-reduce per schedule group
                inside shard_map over the packed-arena wire (see also
                repro.planning for the Plan artifact / policy registry /
                cost sources)
  profiler    — HLO segment cost extraction + collective-traffic parser
"""

from .comm_model import (
    ALGORITHMS,
    AllReduceModel,
    TPU_V5E as TPU_V5E_ICI,
    TpuInterconnect,
    binary_tree,
    fit_affine,
    paper_cluster_model,
    recursive_doubling,
    recursive_halving_doubling,
    ring,
    tpu_psum_model,
)
from .cost_model import Hardware, LayerCost, NVIDIA_K80, TPU_V5E, lm_layer_costs
from .timeline import TimelineResult, evaluate, gradient_avail_times
from .schedule import (
    Schedule,
    evaluate_schedule,
    fixed_bucket_schedule,
    groups_from_merged_set,
    mg_wfbp_schedule,
    optimal_schedule,
    synceasgd_schedule,
    wfbp_schedule,
)
from .bucketing import (
    ArenaSlot,
    CommUnit,
    GroupArena,
    ParamLayout,
    bucket_assignment,
    group_arenas,
    layer_buckets_for_scan,
    layout_for_stacked_lm,
    layout_from_params,
    stacked_lm_layout,
    tree_get,
    tree_set,
)
from .schedule import dp_optimal_schedule
from .sync import (
    SyncConfig,
    make_gradient_sync,
    wire_entries,
)
from .profiler import CollectiveStats, SegmentCost, parse_collectives, segment_cost

__all__ = [
    "ALGORITHMS",
    "AllReduceModel",
    "TPU_V5E_ICI",
    "TpuInterconnect",
    "binary_tree",
    "fit_affine",
    "paper_cluster_model",
    "recursive_doubling",
    "recursive_halving_doubling",
    "ring",
    "tpu_psum_model",
    "Hardware",
    "LayerCost",
    "NVIDIA_K80",
    "TPU_V5E",
    "lm_layer_costs",
    "TimelineResult",
    "evaluate",
    "gradient_avail_times",
    "Schedule",
    "evaluate_schedule",
    "fixed_bucket_schedule",
    "groups_from_merged_set",
    "mg_wfbp_schedule",
    "optimal_schedule",
    "synceasgd_schedule",
    "wfbp_schedule",
    "ArenaSlot",
    "CommUnit",
    "GroupArena",
    "ParamLayout",
    "bucket_assignment",
    "group_arenas",
    "layer_buckets_for_scan",
    "layout_for_stacked_lm",
    "layout_from_params",
    "stacked_lm_layout",
    "tree_get",
    "tree_set",
    "dp_optimal_schedule",
    "SyncConfig",
    "make_gradient_sync",
    "wire_entries",
    "CollectiveStats",
    "SegmentCost",
    "parse_collectives",
    "segment_cost",
]
