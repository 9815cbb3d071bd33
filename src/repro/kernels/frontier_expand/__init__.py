from .ops import (DevicePlan, FrontierPlan, build_frontier_plan,
                  expand_staged, frontier_expand_counts,
                  frontier_expand_launch, stage_frontier, upload_plan)
from .ref import frontier_expand_np, frontier_expand_ref

__all__ = [
    "DevicePlan",
    "FrontierPlan",
    "build_frontier_plan",
    "expand_staged",
    "frontier_expand_counts",
    "frontier_expand_launch",
    "frontier_expand_np",
    "frontier_expand_ref",
    "stage_frontier",
    "upload_plan",
]
