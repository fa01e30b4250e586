"""Operations and bytes that the served model NEEDS, counted from its
published shapes: live context, prompt tokens, parameters.  Not what
one implementation happens to touch (padding, a copied pool, a chunk
re-read), so a later change to the kernels or the engine is judged
against the same yardstick.

Conventions: a multiply-add is 2 FLOPs; attention of one query against
``c`` keys is ``4 * heads * head_dim * c`` FLOPs (scores and the
weighted sum); causal prefill of positions ``[s, s + n)`` attends
``sum(s + 1 .. s + n)`` keys; the logits of a prompt are needed at its
last position only.

``sh`` is a family's work counts (``shapes()`` of
``bench/families/<family>.py``): ``token_flops``, ``head_flops``,
``attn_flops(keys)``, ``layers``, ``heads``, ``kv_heads``, ``head_dim``,
``act_bytes`` and ``kv_bytes``.
"""

from __future__ import annotations


def causal_keys(start: int, n: int) -> int:
    """Keys attended by positions ``[start, start + n)`` under a causal
    mask: ``sum(start + 1 .. start + n)``."""
    return n * start + n * (n + 1) // 2


def prefill_chunk_flops(sh, start: int, n: int, last: bool) -> int:
    """Model FLOPs of prompt positions ``[start, start + n)``; ``last``
    adds the one row of logits a completed prompt needs."""
    return (n * sh.token_flops + sh.attn_flops(causal_keys(start, n))
            + (sh.head_flops if last else 0))


def decode_tick_flops(sh, contexts: list) -> int:
    """Model FLOPs of one decode tick: each live row runs one token
    against its ``c`` live keys and produces one row of logits."""
    return (len(contexts) * (sh.token_flops + sh.head_flops)
            + sh.attn_flops(sum(contexts)))


def flash_chunk_work(sh, start: int, n: int) -> tuple:
    """(FLOPs, bytes) of a prefill chunk's causal attention over all
    layers: read its queries, the keys and values up to its end, write
    its outputs."""
    flops = sh.attn_flops(causal_keys(start, n))
    per_layer = sh.act_bytes * (2 * n * sh.heads * sh.head_dim
                                + 2 * (start + n) * sh.kv_heads
                                * sh.head_dim)
    return flops, sh.layers * per_layer


def paged_decode_work(sh, contexts: list) -> tuple:
    """(FLOPs, bytes) of one decode tick's attention over all layers:
    read every live key and value at the pool's element size, read the
    query and write the output of each live row."""
    keys = sum(contexts)
    flops = sh.attn_flops(keys)
    per_layer = (sh.kv_bytes * 2 * keys * sh.kv_heads * sh.head_dim
                 + sh.act_bytes * 2 * len(contexts) * sh.heads * sh.head_dim)
    return flops, sh.layers * per_layer


def roofline_seconds(flops: float, nbytes: float, peak_flops: float,
                     peak_bw: float) -> tuple:
    """The least time the chip can take, and which bound sets it."""
    tc, tm = flops / peak_flops, nbytes / peak_bw
    return (tc, "compute") if tc >= tm else (tm, "memory")
