"""batch_occupancy (%), scheduler layer: decoding rows over slots,
summed over the decode ticks of the window (``ServeMetrics.steps``,
engine time = seconds since the window opened)."""


def read(run):
    close = run.window.closed - run.window.origin
    steps = [s for s in run.engine_metrics.steps if s.t <= close]
    if not steps:
        return None
    return 100.0 * sum(s.live for s in steps) / sum(s.slots for s in steps)
