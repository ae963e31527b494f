"""FusionStitching core: the paper's contribution as a composable JAX module."""
from .costctx import CostContext, NullContext
from .cost_model import Hardware, V5E, best_estimate, delta_evaluator, \
    partition_gain, recompute_cost, reuse_plan, stitch_gain
from .memory_planner import ReusePlan, plan_reuse
from .ir import FusionPlan, Graph, Node, OpKind, Pattern, StitchGroup
from .plan_cache import PlanCache, graph_signature
from .planner import make_plan, plan_stats
from .stitch import StitchedFunction, fusion_report, stitched_jit
from .stitcher import PartitionCandidate, StitchStats, TopKResult, \
    make_groups, search_groups
from .tracer import trace

__all__ = [
    "CostContext", "NullContext",
    "Hardware", "V5E", "best_estimate", "delta_evaluator",
    "partition_gain", "recompute_cost", "reuse_plan", "stitch_gain",
    "ReusePlan", "plan_reuse",
    "FusionPlan", "Graph", "Node", "OpKind", "Pattern", "StitchGroup",
    "PlanCache", "graph_signature",
    "make_plan", "plan_stats",
    "StitchedFunction", "fusion_report", "stitched_jit",
    "PartitionCandidate", "StitchStats", "TopKResult",
    "make_groups", "search_groups",
    "trace",
]
