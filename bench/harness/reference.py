"""What every family's plain reference shares (``bench/families/<family>.py``
composes its layers from these): float32 jax.numpy at the highest
matmul precision, with no kernel, cache, batching or chunking.  It
imports nothing of the program.

Two conventions are the program's and are followed here: RMSNorm gains
are stored as offsets from 1 (the gain is ``1 + g``), and rotary
embedding rotates the two halves of each head (``rotate_half``, as in
the published Llama and Qwen3 code).

``quant="fp8"`` is the control: every matmul's weights (per output
channel) and inputs (per row) are rounded to float8 e4m3 with absmax
scaling, the precision below the configuration's bfloat16.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
#: queries per block of the attention sweep (bounds the score matrix)
Q_BLOCK = 512
#: sequences are padded to a multiple of this (fewer compiled lengths);
#: padding sits after every wanted position, so the causal mask hides it
PAD_TO = 512
#: wanted positions are padded (repeating the last) to a multiple of this
WANT_TO = 128
E4M3_MAX = 448.0


def _fp8(x, axis):
    """Round to float8 e4m3 with absmax scaling over ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / E4M3_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def mm(spec, x, w, quant, x_axis, w_axis):
    if quant == "fp8":
        x, w = _fp8(x, x_axis), _fp8(w, w_axis)
    return jnp.einsum(spec, x, w, precision=HI)


def rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * (1.0 + g)


def rope(x, pos, theta):
    """x (S, n, hd): rotate the two halves by position-dependent angles."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * freqs
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def attention(q, k, v):
    """Causal GQA: q (S, G, R, hd), k/v (S, G, hd) -> (S, G, R, hd),
    swept in blocks of queries."""
    s, g, r, hd = q.shape
    scale = hd ** -0.5
    kpos = jnp.arange(s)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.einsum("qgrd,kgd->grqk", qb, k, precision=HI) * scale
        sc = jnp.where(kpos[None, None, None, :] <= qpos[None, None, :, None],
                       sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("grqk,kgd->qgrd", p, v, precision=HI)

    out = jax.lax.map(block, jnp.arange(s // Q_BLOCK))
    return out.reshape(s, g, r, hd)


@functools.lru_cache(maxsize=None)
def _compiled(forward, sz_items: tuple, quant):
    return jax.jit(functools.partial(forward, sz=dict(sz_items), quant=quant))


def run(forward, sz_items: tuple, params, tokens, want,
        quant=None) -> np.ndarray:
    """``forward(params, tokens, want, sz=..., quant=...)`` jitted once
    per sizes and precision, over ``tokens`` padded to ``PAD_TO`` and
    ``want`` to ``WANT_TO``: float32 logits (len(want), vocab)."""
    n = len(tokens)
    padded = np.zeros(-(-n // PAD_TO) * PAD_TO, np.int32)
    padded[:n] = tokens
    w = np.asarray(want, np.int32)
    wp = np.full(-(-len(w) // WANT_TO) * WANT_TO, w[-1], np.int32)
    wp[:len(w)] = w
    out = _compiled(forward, sz_items, quant)(params, jnp.asarray(padded),
                                              jnp.asarray(wp))
    return np.asarray(out, np.float32)[:len(w)]
