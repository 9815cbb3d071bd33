"""Share of the window spent in kernel-mode relax rounds of `sssp`: the
`multihop.relax` telemetry spans tagged `mode=kernel`, summed, over the
window's host seconds, in %. Nothing to read where the window ran no
kernel-mode round (a program without the span ran none)."""
from __future__ import annotations


def read(name, reading):
    kernel_us = [s["dur"] for s in reading.spans
                 if s["name"] == "multihop.relax"
                 and s["args"].get("mode") == "kernel"]
    if not kernel_us:
        return None
    return 100.0 * sum(kernel_us) * 1e-6 / reading.window.elapsed_s
