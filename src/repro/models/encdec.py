"""whisper-medium — encoder-decoder backbone (arXiv:2212.04356).

The conv/mel frontend is a STUB per the assignment: the encoder consumes
precomputed frame embeddings (B, T_enc, d) from ``input_specs()``.
Adaptation notes (DESIGN.md): sinusoidal encoder positions are added on
the fly; the decoder uses RoPE instead of Whisper's learned absolute
embeddings (positional flavour is irrelevant to the mapping study and
RoPE keeps the decode path cache-length-agnostic)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.attention import (attention_block, attention_decode,
                                    attention_specs, chunked_attention)
from repro.models.layers import (ParamSpec, ShardCtx, embed, embed_specs,
                                 mlp, mlp_specs, rmsnorm, rope_tables,
                                 stack_specs, unembed)


def _enc_block_specs(cfg: ModelConfig) -> dict:
    return {
        "ln1": ParamSpec((cfg.d_model,), ("embed",), init="zeros"),
        "attn": attention_specs(cfg),
        "ln2": ParamSpec((cfg.d_model,), ("embed",), init="zeros"),
        "mlp": mlp_specs(cfg),
    }


def _dec_block_specs(cfg: ModelConfig) -> dict:
    s = _enc_block_specs(cfg)
    s["ln_x"] = ParamSpec((cfg.d_model,), ("embed",), init="zeros")
    s["cross"] = attention_specs(cfg)
    return s


def encdec_model_specs(cfg: ModelConfig) -> dict:
    return {
        "embed": embed_specs(cfg),
        "enc_blocks": stack_specs(_enc_block_specs(cfg), cfg.encoder_layers),
        "enc_ln_f": ParamSpec((cfg.d_model,), ("embed",), init="zeros"),
        "dec_blocks": stack_specs(_dec_block_specs(cfg), cfg.num_layers),
        "ln_f": ParamSpec((cfg.d_model,), ("embed",), init="zeros"),
    }


def _sinusoid(t: int, d: int) -> jax.Array:
    pos = jnp.arange(t, dtype=jnp.float32)[:, None]
    i = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    ang = pos / (10000.0 ** (2 * i / d))
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)


def _cross_kv(cross_params, enc_out):
    k = jnp.einsum("btd,dgk->btgk", enc_out, cross_params["wk"])
    v = jnp.einsum("btd,dgk->btgk", enc_out, cross_params["wv"])
    return k, v


def _cross_attn(cross_params, x, k, v, cfg, ctx):
    b, s, _ = x.shape
    g = max(cfg.num_kv_heads, 1)
    r = cfg.num_heads // g
    q = jnp.einsum("bsd,dhk->bshk", x, cross_params["wq"])
    q = ctx.p(q, "batch", None, "heads", None)
    o = chunked_attention(q.reshape(b, s, g, r, cfg.head_dim), k, v,
                          causal=False)
    return jnp.einsum("bshk,hkd->bsd", o.reshape(b, s, -1, cfg.head_dim),
                      cross_params["wo"])


def encode(params: dict, frames: jax.Array, cfg: ModelConfig, *,
           remat: str = "none", ctx: ShardCtx) -> jax.Array:
    """frames (B, T_enc, d) stub embeddings -> encoder states."""
    x = frames + _sinusoid(frames.shape[1], cfg.d_model).astype(frames.dtype)
    x = ctx.p(x, "batch", "seq_sp", "embed")

    def body(x, lp):
        lp = jax.lax.optimization_barrier(lp)
        h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
        a, _ = attention_block(lp["attn"], h, cfg, causal=False, ctx=ctx)
        x = ctx.p(x + a, "batch", "seq_sp", "embed")
        h = rmsnorm(x, lp["ln2"], cfg.norm_eps)
        return ctx.p(x + mlp(lp["mlp"], h, cfg.mlp_act, ctx),
                     "batch", "seq_sp", "embed"), None

    if remat != "none":
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    x, _ = jax.lax.scan(body, x, params["enc_blocks"])
    return rmsnorm(x, params["enc_ln_f"], cfg.norm_eps)


def encdec_forward(params: dict, tokens: jax.Array, frames: jax.Array,
                   cfg: ModelConfig, *, remat: str = "none",
                   return_cache: bool = False, prefill_tiles=None,
                   ctx: ShardCtx):
    """Teacher-forced decode over `tokens` given encoder `frames`.

    ``prefill_tiles`` parameterizes the executed decoder self-attention
    (the length that buckets in serving); the encoder and the
    cross-attention run at the static ``encoder_tokens`` length and keep
    the GSPMD path."""
    enc = encode(params, frames, cfg, remat=remat, ctx=ctx)
    x = embed(params["embed"], tokens)
    x = ctx.p(x, "batch", "seq_sp", "embed")
    s = x.shape[1]
    cos, sin = rope_tables(jnp.arange(s), cfg.head_dim, cfg.rope_theta)

    def body(x, lp):
        lp = jax.lax.optimization_barrier(lp)
        h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
        a, kv = attention_block(lp["attn"], h, cfg, cos=cos, sin=sin,
                                causal=True, prefill_tiles=prefill_tiles,
                                ctx=ctx)
        x = ctx.p(x + a, "batch", "seq_sp", "embed")
        ck, cv = _cross_kv(lp["cross"], enc)
        h = rmsnorm(x, lp["ln_x"], cfg.norm_eps)
        x = x + _cross_attn(lp["cross"], h, ck, cv, cfg, ctx)
        h = rmsnorm(x, lp["ln2"], cfg.norm_eps)
        x = ctx.p(x + mlp(lp["mlp"], h, cfg.mlp_act, ctx),
                  "batch", "seq_sp", "embed")
        return x, ((kv, (ck, cv)) if return_cache else None)

    if remat != "none":
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    x, caches = jax.lax.scan(body, x, params["dec_blocks"])
    x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
    logits = unembed(params["embed"], x, ctx)
    if return_cache:
        return logits, jnp.float32(0.0), caches
    return logits, jnp.float32(0.0)


# --------------------------------------------------------------------------- #
# Decode
# --------------------------------------------------------------------------- #


def encdec_init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype,
                      abstract: bool = False, cache_dtype=None) -> dict:
    g = max(cfg.num_kv_heads, 1)
    l, t = cfg.num_layers, cfg.encoder_tokens
    # cache_dtype quantizes only the growing self-attention k/v (the
    # paged pool); the static cross-attention ck/cv stay model dtype
    kv_dt = jnp.dtype(cache_dtype) if cache_dtype is not None else dtype
    mk = (lambda s, dt: jax.ShapeDtypeStruct(s, dt)) if abstract else \
         (lambda s, dt: jnp.zeros(s, dt))
    return {
        "k": mk((l, batch, max_len, g, cfg.head_dim), kv_dt),
        "v": mk((l, batch, max_len, g, cfg.head_dim), kv_dt),
        "ck": mk((l, batch, t, g, cfg.head_dim), dtype),
        "cv": mk((l, batch, t, g, cfg.head_dim), dtype),
        "pos": mk((), jnp.int32),
    }


def encdec_decode(params: dict, cache: dict, tokens: jax.Array,
                  cfg: ModelConfig, *, ctx: ShardCtx,
                  decode_block=None, page_tables=None, page_block=None,
                  paged_decode_block=None):
    """One decoder step.  ``cache["pos"]`` may be a scalar (fixed batch)
    or a (B,) vector (the serving pool's ragged rows); ``decode_block``
    is the bucket-tuned attention sweep mapping (see
    ``attention.attention_decode``).  Cross-attention KV is static per
    request, so only self-attention consumes the tuned block — and only
    the self-attention caches page under ``page_tables`` (and fuse the
    table read under ``paged_decode_block``)."""
    x = embed(params["embed"], tokens)
    pos = cache["pos"]
    rope_pos = pos[:, None] if pos.ndim else pos[None]
    cos, sin = rope_tables(rope_pos, cfg.head_dim, cfg.rope_theta)

    quant = "k_scale" in cache   # int8 paged pool: scales ride the scan

    def body(x, xs):
        if quant:
            lp, kc, vc, ks, vs, ck, cv = jax.lax.optimization_barrier(xs)
        else:
            lp, kc, vc, ck, cv = jax.lax.optimization_barrier(xs)
            ks = vs = None
        h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
        a, kv = attention_decode(lp["attn"], h, cfg, kc, vc, pos,
                                 cos=cos, sin=sin,
                                 decode_block=decode_block,
                                 page_tables=page_tables,
                                 page_block=page_block,
                                 paged_decode_block=paged_decode_block,
                                 k_scale=ks, v_scale=vs, ctx=ctx)
        x = x + a
        h = rmsnorm(x, lp["ln_x"], cfg.norm_eps)
        x = x + _cross_attn(lp["cross"], h, ck, cv, cfg, ctx)
        h = rmsnorm(x, lp["ln2"], cfg.norm_eps)
        x = x + mlp(lp["mlp"], h, cfg.mlp_act, ctx)
        return x, kv

    xs = (params["dec_blocks"], cache["k"], cache["v"])
    if quant:
        xs = xs + (cache["k_scale"], cache["v_scale"])
    x, kv_new = jax.lax.scan(body, x, xs + (cache["ck"], cache["cv"]))
    x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
    logits = unembed(params["embed"], x, ctx)
    out = {"k": kv_new[0], "v": kv_new[1], "ck": cache["ck"],
           "cv": cache["cv"], "pos": pos + 1}
    if quant:
        out["k_scale"], out["v_scale"] = kv_new[2], kv_new[3]
    return logits, out
