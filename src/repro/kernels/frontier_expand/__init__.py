from .ops import (FrontierPlan, StagedFrontier, build_frontier_plan,
                  expand_staged, frontier_expand_counts, stage_frontier)
from .ref import frontier_expand_np, frontier_expand_ref

__all__ = [
    "FrontierPlan",
    "StagedFrontier",
    "build_frontier_plan",
    "expand_staged",
    "frontier_expand_counts",
    "frontier_expand_np",
    "frontier_expand_ref",
    "stage_frontier",
]
