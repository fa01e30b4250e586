"""kv_live_share (%), KV pool layer: the context the window's decode
ticks read (summed over the rows each decodes) over what the pool holds
for them (slots times pool length, per tick), from the engine's
``decode_tick`` spans (``ctx_tokens``, ``slots``, ``pool_len``) that
lie in the window."""


def read(run):
    w = run.window
    ticks = [sp.attrs for sp in run.spans
             if sp.name == "decode_tick"
             and w.origin <= sp.t0 and sp.t0 + sp.dur <= w.closed
             and sp.attrs.get("ctx_tokens") is not None]
    held = sum(a["slots"] * a["pool_len"] for a in ticks)
    if not held:
        return None
    return 100.0 * sum(a["ctx_tokens"] for a in ticks) / held
