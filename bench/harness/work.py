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
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Shapes:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    act_bytes: int = 2        # bf16 activations
    kv_bytes: int = 2         # pool element size

    @classmethod
    def from_sizes(cls, s: dict, kv_bytes: int = 2) -> "Shapes":
        return cls(layers=s["num_hidden_layers"], d=s["hidden_size"],
                   heads=s["num_attention_heads"],
                   kv_heads=s["num_key_value_heads"], head_dim=s["head_dim"],
                   d_ff=s["intermediate_size"], vocab=s["vocab_size"],
                   kv_bytes=kv_bytes)

    @property
    def layer_matmul_params(self) -> int:
        """Weights one token multiplies per layer: q, k, v, o and the
        gated MLP (gate, up, down)."""
        d, h, g, hd = self.d, self.heads, self.kv_heads, self.head_dim
        return d * h * hd + 2 * d * g * hd + h * hd * d + 3 * d * self.d_ff

    @property
    def token_flops(self) -> int:
        """Matmul FLOPs of one token through every layer (no attention
        scores, no head)."""
        return 2 * self.layers * self.layer_matmul_params

    @property
    def head_flops(self) -> int:
        """FLOPs of one token's logits."""
        return 2 * self.d * self.vocab

    def attn_flops(self, keys: int) -> int:
        """Attention FLOPs over all layers for ``keys`` (query, key)
        pairs per head."""
        return 4 * self.layers * self.heads * self.head_dim * keys


def causal_keys(start: int, n: int) -> int:
    """Keys attended by positions ``[start, start + n)`` under a causal
    mask: ``sum(start + 1 .. start + n)``."""
    return n * start + n * (n + 1) // 2


def prefill_chunk_flops(sh: Shapes, start: int, n: int,
                        last: bool) -> int:
    """Model FLOPs of prompt positions ``[start, start + n)``; ``last``
    adds the one row of logits a completed prompt needs."""
    return (n * sh.token_flops + sh.attn_flops(causal_keys(start, n))
            + (sh.head_flops if last else 0))


def decode_tick_flops(sh: Shapes, contexts: list) -> int:
    """Model FLOPs of one decode tick: each live row runs one token
    against its ``c`` live keys and produces one row of logits."""
    return (len(contexts) * (sh.token_flops + sh.head_flops)
            + sh.attn_flops(sum(contexts)))


def flash_chunk_work(sh: Shapes, start: int, n: int) -> tuple:
    """(FLOPs, bytes) of a prefill chunk's causal attention over all
    layers: read its queries, the keys and values up to its end, write
    its outputs."""
    flops = sh.attn_flops(causal_keys(start, n))
    per_layer = sh.act_bytes * (2 * n * sh.heads * sh.head_dim
                                + 2 * (start + n) * sh.kv_heads
                                * sh.head_dim)
    return flops, sh.layers * per_layer


def paged_decode_work(sh: Shapes, contexts: list) -> tuple:
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
