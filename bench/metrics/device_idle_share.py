"""device_idle_share (%), device layer: 1 minus the union of the
device's operation intervals over the traced window (the first to the
last harness host span in the profiler trace)."""

from harness import trace


def read(run):
    if run.trace is None or not run.trace["devices"]:
        return None
    lo, hi = trace.window_of(run.trace)
    return 100.0 * (1.0 - trace.busy_s(run.trace) / ((hi - lo) / 1e9))
