"""paged_decode_roofline (%), kernels layer: the least time the chip
needs to read the live keys and values (plus queries and outputs) of
the decode ticks run while the profiler recorded
(``harness.work.paged_decode_work``), over the summed device time of
the fused paged decode kernel's events in the trace."""

from harness import trace, work


def read(run):
    if run.trace is None or run.traced is None or run.peaks is None:
        return None
    sh = run.shapes
    # one layer's attention output for the whole batch:
    # (slots, kv heads, query heads per kv head, head_dim)
    out = (run.slots, sh.kv_heads, sh.heads // sh.kv_heads, sh.head_dim)
    secs, n, _ = trace.kernel_seconds(run.trace, lambda d: d == out)
    ticks = run.ticks(*run.traced)
    if n == 0 or secs <= 0 or not ticks:
        return None
    least = sum(work.roofline_seconds(*work.paged_decode_work(sh, c),
                                      run.peaks.bf16_flops,
                                      run.peaks.hbm_bytes_per_s)[0]
                for c in ticks)
    return 100.0 * least / secs
