"""Bytes handed from the host to the device per kernel-mode hop, in 10^6
bytes: the window's change in `multihop.kernel.h2d_bytes` (the `.nbytes`
of every host array a frontier-expansion launch uploads) over its change
in kernel-labelled `multihop.hops`. Nothing to read where the window ran
no kernel hop or the program keeps no such counter."""
from __future__ import annotations

COUNTER = "multihop.kernel.h2d_bytes"


def _kernel_hops(counters):
    hops = counters.get("multihop.hops", {})
    return hops.get("kernel", 0) if isinstance(hops, dict) else 0


def read(name, reading):
    before = reading.counters_before["counters"]
    after = reading.counters_after["counters"]
    hops = _kernel_hops(after) - _kernel_hops(before)
    if COUNTER not in after or hops <= 0:
        return None
    return (after[COUNTER] - before.get(COUNTER, 0)) / hops / 1e6
