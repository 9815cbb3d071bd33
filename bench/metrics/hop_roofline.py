"""The kernel-mode hops' share of their roofline: the number of
`multihop.hop` spans tagged `mode=kernel` in the window, times the least
time one dense hop over every distinct edge needs on this chip
(bench/roofline.py), over the device's busy time in the trace, in %.
Nothing to read without a trace, a device row or a kernel-mode hop."""
from __future__ import annotations

from bench import roofline


def read(name, reading):
    facts = reading.window.facts
    hops = sum(1 for s in reading.spans if s["name"] == "multihop.hop"
               and s["args"].get("mode") == "kernel")
    if (reading.trace is None or reading.peaks is None or hops == 0
            or "n_edges_distinct" not in facts):
        return None
    flops, nbytes = roofline.frontier_hop_work(
        facts["n_vertices"], facts["n_edges_distinct"],
        facts["frontier_columns"])
    least = hops * roofline.least_seconds(flops, nbytes, reading.peaks)
    return 100.0 * least / reading.trace["busy_s"]
