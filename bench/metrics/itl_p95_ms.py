"""itl_p95_ms (ms): 95th percentile over every gap between consecutive
output tokens of every request, for gaps that end inside the window.
Taken from per-token stamps, so a stall inside a request shows; tokens
that reached the client together give a gap of 0."""

from harness.stats import percentile, token_gaps


def read(run):
    v = token_gaps(run.stamps(), run.window.end)
    return 1e3 * percentile(v, 95) if v else None
