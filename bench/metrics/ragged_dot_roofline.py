"""ragged_dot_roofline (%), kernels layer: the least time the chip needs
for the grouped matmuls of the expert layer in the decode ticks and
prefill chunks run while the profiler recorded, over the device time of
those kernels inside them.

The work of each ``serve.decode_tick`` and ``serve.prefill_chunk`` span
comes from its attributes (``moe_routed``: routed pairs on the experts
held here, ``moe_touched``: held experts with at least one pair, both
summed over layers) through the family's ``ragged_dot_work``: FLOPs of
the pairs' gate, up and down matmuls; bytes of the touched experts'
weights and of each kernel's rows in and out.  ``jax.lax.ragged_dot``
runs on the TPU as XLA's grouped-matmul kernel, whose operations the
trace names ``%ragged-dot-none.<n>`` (gate, up, down) beside
``%ragged-dot-metadata`` (the group offsets); the op events on a v5e
carry no metadata, so the kernel's name is what is matched.

The rest of the expert layer is in neither the work nor the time: the
router matmul, softmax and top-k, the sort of the pairs by expert, the
gather of each pair's row, and the gated scatter-add back to the
tokens.  A run whose spans carry no such attributes has nothing to read.
"""

from harness import program, work

#: the name of XLA's TPU grouped-matmul kernel in the trace
KERNEL = "ragged-dot"
SPANS = ("serve.decode_tick", "serve.prefill_chunk")


def read(run):
    if run.peaks is None or not hasattr(run.shapes, "ragged_dot_work"):
        return None
    evs = program.events(run)
    if not evs or not run.trace["devices"]:
        return None
    ops = program.named_ops(program.first_chip(run.trace), KERNEL)
    least = secs = 0.0
    for name, s, e, attrs in evs:
        if name not in SPANS or "moe_routed" not in attrs:
            continue
        t = program.busy_ns(ops, s, e) / 1e9
        if t <= 0:
            continue
        w = run.shapes.ragged_dot_work(int(attrs["moe_routed"]),
                                       int(attrs["moe_touched"]))
        least += work.roofline_seconds(*w, run.peaks.bf16_flops,
                                       run.peaks.hbm_bytes_per_s)[0]
        secs += t
    if secs <= 0:
        return None
    return 100.0 * least / secs
