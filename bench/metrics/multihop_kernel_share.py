"""Share of the window spent in kernel-mode hops of the columnar
operators: the `multihop.hop` telemetry spans tagged `mode=kernel`, summed,
over the window's host seconds, in %. Nothing to read where the window
ran no hop at all."""
from __future__ import annotations


def read(name, reading):
    hops = [s for s in reading.spans if s["name"] == "multihop.hop"]
    if not hops:
        return None
    kernel_us = sum(s["dur"] for s in hops
                    if s["args"].get("mode") == "kernel")
    return 100.0 * kernel_us * 1e-6 / reading.window.elapsed_s
