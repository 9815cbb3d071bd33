"""The kernel-mode relax rounds' share of their roofline: the number of
`multihop.relax` spans tagged `mode=kernel` in the window, times the least
time one min-plus relax over every distinct edge needs on this chip, over
the device's busy time in the trace, in %. Nothing to read without a
trace, a device row or a kernel-mode round."""
from __future__ import annotations

from bench import roofline


def relax_work(n_vertices: int, n_edges: int):
    """One min-plus relax over every distinct edge: read each edge's source
    id and weight (8 bytes), read the distance panel and write the
    candidates (8 bytes per vertex); an add and a min per edge. Never the
    plan's padded rows. Returns (flops, bytes)."""
    return 2 * n_edges, 8 * n_edges + 8 * n_vertices


def read(name, reading):
    facts = reading.window.facts
    rounds = sum(1 for s in reading.spans if s["name"] == "multihop.relax"
                 and s["args"].get("mode") == "kernel")
    if (reading.trace is None or reading.peaks is None or rounds == 0
            or "n_edges_distinct" not in facts):
        return None
    flops, nbytes = relax_work(facts["n_vertices"],
                               facts["n_edges_distinct"])
    least = rounds * roofline.least_seconds(flops, nbytes, reading.peaks)
    return 100.0 * least / reading.trace["busy_s"]
