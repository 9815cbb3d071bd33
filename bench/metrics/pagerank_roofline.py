"""Device PageRank's share of its roofline: iterations completed in the
window times the least time one sweep over every stored edge needs on
this chip (bench/roofline.py), over the device's busy time in the trace,
in %. Nothing to read without a trace, a device row or an iteration."""
from __future__ import annotations

from bench import roofline


def read(name, reading):
    facts = reading.window.facts
    if (reading.trace is None or reading.peaks is None
            or not facts.get("iterations")):
        return None
    flops, nbytes = roofline.pagerank_sweep_work(facts["n_vertices"],
                                                 facts["n_edges"])
    least = facts["iterations"] * roofline.least_seconds(flops, nbytes,
                                                         reading.peaks)
    return 100.0 * least / reading.trace["busy_s"]
