"""decode_mfu (%), model step layer: model FLOPs that the window's
decode ticks need (one token per live row against its live context,
``harness.work``), over the engine's summed decode-step seconds in the
window (its ``decode_s`` counter) times the chip's bf16 peak."""

from harness import work


def read(run):
    w = run.window
    ticks = run.ticks(w.origin, w.closed)
    if not ticks or w.decode_s <= 0 or run.peaks is None:
        return None
    flops = sum(work.decode_tick_flops(run.shapes, c) for c in ticks)
    return 100.0 * flops / (w.decode_s * run.peaks.bf16_flops)
