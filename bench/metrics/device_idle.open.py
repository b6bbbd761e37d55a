"""device_idle.open: 1 − (union of the device's program intervals) /
(traced window), from the profiler trace (`bench/trace.py`)."""


def read(window):
    tr = window.trace
    if tr["busy_s"] <= 0:
        return None
    return tr["idle_share"]
