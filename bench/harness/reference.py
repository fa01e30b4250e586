"""The plain reference: the published decoder (Llama / Qwen3 dense
layers) in float32 jax.numpy at the highest matmul precision, with no
kernel, cache, batching or chunking.  It imports nothing of the program.

It reads the weights as a dict keyed by the program's leaf names
(``embed/tok``, ``blocks/attn/wq`` ...), stacked over layers, and
upcasts one layer at a time inside a scan, so float32 copies of all the
weights never exist at once.  Two conventions are the program's and are
followed here: RMSNorm gains are stored as offsets from 1 (the gain is
``1 + g``), and rotary embedding rotates the two halves of each head
(``rotate_half``, as in the published Llama and Qwen3 code).

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


def _mm(spec, x, w, quant, x_axis, w_axis):
    if quant == "fp8":
        x, w = _fp8(x, x_axis), _fp8(w, w_axis)
    return jnp.einsum(spec, x, w, precision=HI)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * (1.0 + g)


def _rope(x, pos, theta):
    """x (S, n, hd): rotate the two halves by position-dependent angles."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * freqs
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _attention(q, k, v):
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


def _forward(params, tokens, want, *, sz, quant):
    L, d = sz["num_hidden_layers"], sz["hidden_size"]
    h, g, hd = (sz["num_attention_heads"], sz["num_key_value_heads"],
                sz["head_dim"])
    eps, theta = float(sz["rms_norm_eps"]), float(sz["rope_theta"])
    qk_norm = bool(sz.get("qk_norm", False))
    tok = params["embed"]["tok"]
    x = tok[tokens].astype(F32)
    pos = jnp.arange(tokens.shape[0])

    def layer(x, p):
        p = jax.tree.map(lambda a: a.astype(F32), p)
        a = p["attn"]
        hn = _rms(x, p["ln1"], eps)
        q = _mm("sd,dhk->shk", hn, a["wq"], quant, -1, 0)
        k = _mm("sd,dgk->sgk", hn, a["wk"], quant, -1, 0)
        v = _mm("sd,dgk->sgk", hn, a["wv"], quant, -1, 0)
        if qk_norm:
            q = _rms(q, a["q_norm"], eps)
            k = _rms(k, a["k_norm"], eps)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        o = _attention(q.reshape(-1, g, h // g, hd), k, v)
        o = o.reshape(-1, h, hd)
        x = x + _mm("shk,hkd->sd", o, a["wo"], quant, (-2, -1), (0, 1))
        hn = _rms(x, p["ln2"], eps)
        m = p["mlp"]
        gate = _mm("sd,df->sf", hn, m["w_gate"], quant, -1, 0)
        up = _mm("sd,df->sf", hn, m["w_up"], quant, -1, 0)
        x = x + _mm("sf,fd->sd", jax.nn.silu(gate) * up, m["w_down"],
                    quant, -1, 0)
        return x, None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    xw = _rms(x[want], params["ln_f"].astype(F32), eps)
    if "unembed" in params["embed"]:
        return _mm("sd,dv->sv", xw, params["embed"]["unembed"].astype(F32),
                   quant, -1, 0)
    return _mm("sd,vd->sv", xw, tok.astype(F32), quant, -1, -1)


@functools.lru_cache(maxsize=None)
def _compiled(sz_items: tuple, quant):
    return jax.jit(functools.partial(_forward, sz=dict(sz_items),
                                     quant=quant))


def logits(params, sizes: dict, tokens, want, quant=None) -> np.ndarray:
    """float32 logits (len(want), vocab) of the sequence ``tokens`` at
    the positions ``want``."""
    keys = ("num_hidden_layers", "hidden_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "rms_norm_eps", "rope_theta",
            "qk_norm")
    sz = tuple((k, sizes.get(k)) for k in keys)
    n = len(tokens)
    padded = np.zeros(-(-n // PAD_TO) * PAD_TO, np.int32)
    padded[:n] = tokens
    w = np.asarray(want, np.int32)
    wp = np.full(-(-len(w) // WANT_TO) * WANT_TO, w[-1], np.int32)
    wp[:len(w)] = w
    out = _compiled(sz, quant)(params, jnp.asarray(padded), jnp.asarray(wp))
    return np.asarray(out, np.float32)[:len(w)]
