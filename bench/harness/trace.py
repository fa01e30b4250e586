"""From a JAX profiler trace to device busy time, kernel time and idle
gaps, kept as code so that every PR reduces a trace the same way.

``extract`` reads the ``.xplane.pb`` the profiler wrote (with nothing
but JAX's ``ProfileData``) into a small plain dict: each TPU plane's
operations (the "XLA Ops" line) and the harness's host spans (events
named ``bench.*``).  ``reduce`` works on that dict alone, so it can be
checked on a small recorded trace.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Callable, Iterable, Optional

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _stat_text(ev) -> str:
    try:
        return " ".join(f"{k}={v}" for k, v in ev.stats)
    except (TypeError, ValueError):
        return ""


def extract(xplane_path: str) -> dict:
    """``{"devices": {plane: [[name, start_ns, dur_ns, detail], ...]},
    "host": [[name, start_ns, end_ns], ...]}``"""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane_path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                devices[plane.name] = [
                    [ev.name, int(ev.start_ns), int(ev.duration_ns),
                     _stat_text(ev)] for ev in line.events]
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append([ev.name, int(ev.start_ns),
                                     int(ev.end_ns)])
    return {"devices": devices, "host": host}


def merge(intervals: Iterable) -> list:
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def window_of(ex: dict) -> tuple:
    """The traced window: first to last harness host span."""
    if not ex["host"]:
        raise ValueError("the trace holds no harness host spans")
    return (min(h[1] for h in ex["host"]), max(h[2] for h in ex["host"]))


def _clip(ops, lo, hi):
    for name, s, d, detail in ops:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield name, a, b, detail


def busy_s(ex: dict, window: Optional[tuple] = None) -> float:
    """Seconds in which an operation ran, averaged over the chips."""
    lo, hi = window or window_of(ex)
    if not ex["devices"]:
        return 0.0
    total = 0
    for ops in ex["devices"].values():
        total += sum(e - s for s, e in
                     merge((a, b) for _, a, b, _ in _clip(ops, lo, hi)))
    return total / len(ex["devices"]) / 1e9


def op_seconds(ex: dict, window: Optional[tuple] = None) -> dict:
    """Device seconds by operation name, summed over chips."""
    lo, hi = window or window_of(ex)
    out: dict = {}
    for ops in ex["devices"].values():
        for name, a, b, _ in _clip(ops, lo, hi):
            out[name] = out.get(name, 0.0) + (b - a) / 1e9
    return out


#: the output type of an operation's HLO text: ``= bf16[64,3,3,64]``
_OUT = re.compile(r"= \w+\[([\d,]*)\]")


def pallas_output(name: str) -> Optional[tuple]:
    """The output dimensions of a Pallas kernel's device operation, or
    None for any other operation.

    On the TPU a Pallas kernel runs as an HLO ``custom-call`` to
    ``tpu_custom_call``; the trace names the operation by its HLO text,
    ``%<name> = bf16[64,3,3,64]{...} custom-call(...),
    custom_call_target="tpu_custom_call", ...``.  The ``<name>`` comes
    from the transformations around the kernel (``closed_call``,
    ``vmap_vmap_...``) and not from the kernel, so a kernel is known by
    its output shape."""
    if 'custom_call_target="tpu_custom_call"' not in name:
        return None
    m = _OUT.search(name)
    if m is None:
        return None
    return tuple(int(x) for x in m.group(1).split(",") if x)


def kernel_seconds(ex: dict, match: Callable[[tuple], bool],
                   window: Optional[tuple] = None) -> tuple:
    """(device seconds, events, names matched) of the Pallas kernel
    operations whose output dimensions satisfy ``match``; a kernel's
    time."""
    lo, hi = window or window_of(ex)
    secs, n, names = 0.0, 0, set()
    for ops in ex["devices"].values():
        for name, a, b, _ in _clip(ops, lo, hi):
            dims = pallas_output(name)
            if dims is not None and match(dims):
                secs += (b - a) / 1e9
                n += 1
                names.add(name.split(" = ")[0])
    return secs, n, sorted(names)


def idle_gaps(ex: dict, window: Optional[tuple] = None) -> dict:
    """Idle device seconds (first chip) by what the host was doing: the
    innermost harness span covering each gap's midpoint."""
    lo, hi = window or window_of(ex)
    if not ex["devices"]:
        return {}
    ops = next(iter(ex["devices"].values()))
    busy = merge((a, b) for _, a, b, _ in _clip(ops, lo, hi))
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
        # harness spans are sequential: the last one to start covers mid
        j = bisect.bisect_right(starts, mid) - 1
        label = (host[j][0] if j >= 0 and host[j][2] >= mid
                 else "host outside the harness")
        out[label] = out.get(label, 0.0) + (e - s) / 1e9
    return out
