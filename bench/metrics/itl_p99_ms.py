"""itl_p99_ms (ms): 99th percentile over every gap between consecutive
output tokens of every request, for gaps that end inside the window.
Taken from per-token stamps, so a stall inside a request shows; tokens
that reached the client together give a gap of 0.  The highest
percentile with some tens of gaps beyond it in a window (about 5,500
gaps in the chat cell's)."""

from harness.stats import percentile, token_gaps


def read(run):
    v = token_gaps(run.stamps(), run.window.end)
    return 1e3 * percentile(v, 99) if v else None
