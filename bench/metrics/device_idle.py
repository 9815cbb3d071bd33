"""The device's idle share of the traced window: 1 - busy / window, in %,
from bench/trace.py's reduction. One reader for every cell
(`device_idle.<cell kind>`). Nothing to read without a trace."""
from __future__ import annotations


def read(name, reading):
    t = reading.trace
    if t is None or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
