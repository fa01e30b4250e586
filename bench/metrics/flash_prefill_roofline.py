"""flash_prefill_roofline (%), kernels layer: the least time the chip
needs for the causal attention of the prefill chunks run while the
profiler recorded (FLOPs and bytes from their shapes,
``harness.work.flash_chunk_work``), over the summed device time of the
flash kernel's events in the trace."""

from harness import trace, work


def read(run):
    if run.trace is None or run.traced is None or run.peaks is None:
        return None
    sh = run.shapes
    r = sh.heads // sh.kv_heads

    def flash(d):
        # one layer's output for one prompt: (kv heads, query heads per
        # kv head, positions, head_dim)
        return len(d) == 4 and d[:2] == (sh.kv_heads, r) and \
            d[3] == sh.head_dim

    secs, n, _ = trace.kernel_seconds(run.trace, flash)
    chunks = run.chunks(*run.traced)
    if n == 0 or secs <= 0 or not chunks:
        return None
    least = sum(work.roofline_seconds(*work.flash_chunk_work(sh, s, k),
                                      run.peaks.bf16_flops,
                                      run.peaks.hbm_bytes_per_s)[0]
                for s, k, _ in chunks)
    return 100.0 * least / secs
