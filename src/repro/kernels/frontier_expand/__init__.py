from .ops import (DevicePlan, FrontierPlan, build_frontier_plan,
                  expand_staged, frontier_expand_counts,
                  frontier_expand_launch, relax_launch, relax_staged,
                  stage_frontier, upload_plan)
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
    "relax_launch",
    "relax_staged",
    "stage_frontier",
    "upload_plan",
]
