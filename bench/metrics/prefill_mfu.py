"""prefill_mfu (%), model step layer: model FLOPs that the prompt
positions prefilled in the window need (``harness.work``), over the
engine's summed chunk-step seconds in the window (its ``prefill_s``
counter, host clock around each step's ``block_until_ready``) times the
chip's bf16 peak."""

from harness import work


def read(run):
    w = run.window
    chunks = run.chunks(w.origin, w.closed)
    if not chunks or w.prefill_s <= 0 or run.peaks is None:
        return None
    flops = sum(work.prefill_chunk_flops(run.shapes, s, n, last)
                for s, n, last in chunks)
    return 100.0 * flops / (w.prefill_s * run.peaks.bf16_flops)
