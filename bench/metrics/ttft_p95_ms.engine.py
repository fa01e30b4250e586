"""ttft_p95_ms.engine (ms), engine entry layer: 95th percentile, over
every request due in the window, of the time from when it fell due to
when its first token reached the client (the harness's stamp after the
engine step that made it).  A request still queued at the close counts
once it answers in the drain; one that never answers (rejected, or
unanswered when the drain stops) counts at the drain's end
(``RunView.ttfts_s``).  Per layer, not end to end, in
``smollm-135m.chat``: at 44 requests a window its runs spread by 14%,
more than any bound can hold (PERF.md, section 2)."""

from harness.stats import percentile


def read(run):
    v = run.ttfts_s()
    return 1e3 * percentile(v, 95) if v else None
