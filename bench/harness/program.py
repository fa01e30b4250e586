"""The engine's own spans on the profiler's clock: the phases of each
engine iteration (``serve.step`` and the spans nested in it) beside the
device's operations.

While the JAX profiler records, every span of the engine's
``obs.Tracer`` also enters the profiler trace as a ``serve.<name>``
annotation.  ``harness.trace.extract`` keeps only the harness's own
``bench.*`` host events, so ``events`` places the spans the engine kept
(``RunView.spans``, on the tracer's ``perf_counter`` clock) on the
profiler's clock by one offset: each ``bench.run_step`` event holds
exactly one engine ``step`` span, opened a few microseconds after it
opens and closed a few before it closes.

An event is ``[name, start_ns, end_ns, attrs]``, names prefixed
``serve.``, sorted by start.
"""

from __future__ import annotations

import bisect
import statistics
from typing import Optional

from harness import trace

PREFIX = "serve."
RUN_STEP = "bench.run_step"
#: the ``name=`` of the fused paged decode kernel: on the chip it is the
#: start of the kernel operation's name (``%paged_decode_attention.13``)
DECODE_KERNEL = "paged_decode_attention"


def events(run) -> Optional[list]:
    """The engine's spans inside the traced window, on the profiler's
    clock; None when the run was not traced or its engine records no
    ``step`` spans."""
    if run.trace is None or run.traced is None:
        return None
    lo, hi = run.traced
    spans = [s for s in run.spans if lo <= s.t0 and s.t0 + s.dur <= hi]
    # midpoints: a step opens just after its harness span opens and
    # closes just before it closes
    steps = sorted(1e9 * (s.t0 + s.dur / 2) for s in spans
                   if s.name == "step")
    marks = sorted((h[1] + h[2]) / 2 for h in run.trace["host"]
                   if h[0] == RUN_STEP)
    # one engine step per harness step: a count that differs means the
    # pairing, and so the offset, cannot be trusted
    if not steps or len(steps) != len(marks):
        return None
    off = statistics.median(m - t for m, t in zip(marks, steps))
    return sorted(([PREFIX + s.name, int(round(1e9 * s.t0 + off)),
                    int(round(1e9 * (s.t0 + s.dur) + off)), dict(s.attrs)]
                   for s in spans), key=lambda e: (e[1], -e[2]))


def first_chip(ex: dict) -> list:
    """The first chip's operations."""
    return next(iter(ex["devices"].values()), [])


def busy_ns(ops, lo: int, hi: int) -> float:
    """Nanoseconds of ``[lo, hi]`` in which one of ``ops`` ran (their
    union, as ``harness.trace.busy_s`` counts it)."""
    return 1e9 * trace.busy_s({"devices": {"chip": ops}}, (lo, hi))


def named_ops(ops, name: str) -> list:
    """The operations whose name in the trace holds ``name``."""
    return [op for op in ops if name in op[0]]


def idle_paths(ex: dict, program: list, window: Optional[tuple] = None
               ) -> dict:
    """Idle device seconds (first chip) by what the host was doing: the
    innermost harness span covering each gap's midpoint, followed by
    the path of the engine's spans covering it, outermost first
    (``bench.run_step > serve.step > serve.sample``).  The gaps are
    those of ``harness.trace.idle_gaps``, and with no engine span
    covering a gap its label is the one that function gives."""
    lo, hi = window or trace.window_of(ex)
    if not ex["devices"]:
        return {}
    ops = first_chip(ex)
    busy = trace.merge((max(s, lo), min(s + d, hi)) for _, s, d, _ in ops
                       if min(s + d, hi) > max(s, lo))
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    host = sorted(ex["host"], key=lambda h: h[1])
    starts = [h[1] for h in host]
    out: dict = {}
    for s, e in gaps:
        mid = (s + e) / 2
        j = bisect.bisect_right(starts, mid) - 1
        label = (host[j][0] if j >= 0 and host[j][2] >= mid
                 else "host outside the harness")
        path = [ev[0] for ev in program if ev[1] <= mid <= ev[2]]
        label = " > ".join([label] + path)
        out[label] = out.get(label, 0.0) + (e - s) / 1e9
    return out
