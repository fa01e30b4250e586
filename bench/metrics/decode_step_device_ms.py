"""decode_step_device_ms (ms), model step layer: the median, over the
engine's ``serve.decode_tick`` spans that lie wholly in the traced
window and hold the named paged decode kernel
(``paged_decode_attention``), of the device time (first chip, union of
operations) inside each.  The host dispatches the decode step and waits
on it inside the span, after the previous sample read back everything
queued before it, so the operations there are the decode step
program's (``harness.program``)."""

import statistics

from harness import program, trace


def read(run):
    evs = program.events(run)
    if not evs or not run.trace["devices"]:
        return None
    lo, hi = trace.window_of(run.trace)
    ops = program.first_chip(run.trace)
    kernel = program.named_ops(ops, program.DECODE_KERNEL)
    ticks = [(s, e) for name, s, e, _ in evs
             if name == "serve.decode_tick" and lo <= s and e <= hi
             and program.busy_ns(kernel, s, e) > 0]
    if not ticks:
        return None
    return statistics.median(program.busy_ns(ops, s, e)
                             for s, e in ticks) / 1e6
