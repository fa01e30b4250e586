"""engine_idle_ms_per_step (ms), engine loop layer: device-idle time
(first chip: the window less the union of its operations, as in
``harness.trace.busy_s``) inside the engine's ``serve.step`` spans that
lie wholly in the traced window, over the number of those spans.  The
spans are the engine's own, placed on the profiler's clock
(``harness.program.events``)."""

from harness import program, trace


def read(run):
    evs = program.events(run)
    if not evs or not run.trace["devices"]:
        return None
    lo, hi = trace.window_of(run.trace)
    steps = [(s, e) for name, s, e, _ in evs
             if name == "serve.step" and lo <= s and e <= hi]
    if not steps:
        return None
    ops = program.first_chip(run.trace)
    idle = sum((e - s) - program.busy_ns(ops, s, e) for s, e in steps)
    return idle / len(steps) / 1e6
