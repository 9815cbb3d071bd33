from .ops import FrontierPlan, build_frontier_plan, frontier_expand_counts
from .ref import frontier_expand_np, frontier_expand_ref

__all__ = [
    "FrontierPlan",
    "build_frontier_plan",
    "frontier_expand_counts",
    "frontier_expand_np",
    "frontier_expand_ref",
]
