"""What a metric reader sees of one run (``bench/metrics/<name>.py``
each define ``read(run) -> float | None``; ``None`` means the run has
nothing for that metric to read, and the metric is left out)."""

from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass
class RunView:
    cell_name: str
    seconds: float
    shapes: object                # the family's work counts (shapes())
    slots: int                    # rows of the engine's decode batch
    peaks: object                 # harness.peaks.Peaks (None in rehearsal)
    window: object                # harness.driver.Window
    setup_s: float
    engine_metrics: object        # the engine's ServeMetrics after the run
    spans: list                   # the engine's obs spans (--trace 1)
    trace: Optional[dict] = None  # harness.trace.extract output (--trace 1)

    # -- the window as the client saw it ----------------------------------

    def ttfts_s(self) -> list:
        """Seconds from due to first token, every request due in the
        window.  One that got no first token (rejected, or still waiting
        when the drain stopped) counts at the drain's end: a time it
        waited at least, so the tail rises with it."""
        end = self.window.drain_end
        return [(r.stamps[0] if r.stamps else end) - r.due
                for r in self.window.recs]

    def stamps(self) -> list:
        return [r.stamps for r in self.window.recs]

    def plen_by_rid(self) -> dict:
        return {r.req.rid: r.plen for r in self.window.recs}

    # -- work inside an interval of host time -----------------------------

    def chunks(self, lo: float, hi: float) -> list:
        """(start, n, last) of every prefill chunk the engine ran in
        [lo, hi], from its ``prefill_chunk`` spans."""
        plen = self.plen_by_rid()
        out = []
        for sp in self.spans:
            if sp.name != "prefill_chunk" or not (lo <= sp.t0 and
                                                  sp.t0 + sp.dur <= hi):
                continue
            p = plen.get(sp.attrs.get("rid"))
            if p is None:
                continue
            start = int(sp.attrs["start"])
            n = min(int(sp.attrs["chunk"]), p - start)
            out.append((start, n, start + n >= p))
        return out

    def ticks(self, lo: float, hi: float) -> list:
        """Live contexts of every decode tick the harness saw end in
        (lo, hi]."""
        return [c for t, c in self.window.ticks if lo < t <= hi]

    @property
    def traced(self) -> tuple:
        return self.window.traced
