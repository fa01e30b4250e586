"""zamba2 — Mamba2 backbone with a single SHARED attention block applied
every ``hybrid_attn_every`` layers (arXiv:2411.15242).

Structure: layers are padded to ``n_groups x k`` and scanned as groups —
each group = shared attention block (own KV-cache slot) followed by k
mamba layers (padded layers carry an ``active=False`` flag and pass
through).  The shared block's params are NOT stacked: one copy, reused by
every invocation — the defining Zamba trick (attention quality at ~1/14th
of the attention parameter cost)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.hw import ceil_div
from repro.models.attention import (attention_block, attention_decode,
                                    attention_specs)
from repro.models.layers import (ParamSpec, ShardCtx, embed, embed_specs,
                                 mlp, mlp_specs, rmsnorm, rope_tables,
                                 stack_specs, unembed)
from repro.models.ssm import (ssm_block, ssm_block_specs, ssm_cache_shape,
                              ssm_decode_step)


def n_groups(cfg: ModelConfig) -> int:
    return ceil_div(cfg.num_layers, cfg.hybrid_attn_every)


def padded_layers(cfg: ModelConfig) -> int:
    return n_groups(cfg) * cfg.hybrid_attn_every


def hybrid_model_specs(cfg: ModelConfig) -> dict:
    return {
        "embed": embed_specs(cfg),
        "shared": {
            "ln1": ParamSpec((cfg.d_model,), ("embed",), init="zeros"),
            "attn": attention_specs(cfg),
            "ln2": ParamSpec((cfg.d_model,), ("embed",), init="zeros"),
            "mlp": mlp_specs(cfg),
        },
        "blocks": stack_specs(ssm_block_specs(cfg), padded_layers(cfg)),
        "ln_f": ParamSpec((cfg.d_model,), ("embed",), init="zeros"),
    }


def _active_flags(cfg: ModelConfig) -> jax.Array:
    return (jnp.arange(padded_layers(cfg)) < cfg.num_layers)


def _group(tree, ng: int, k: int):
    return jax.tree.map(lambda a: a.reshape(ng, k, *a.shape[1:]), tree)


def _shared_attn(shared, x, cfg, cos, sin, ctx, prefill_tiles=None):
    h = rmsnorm(x, shared["ln1"], cfg.norm_eps)
    a, kv = attention_block(shared["attn"], h, cfg, cos=cos, sin=sin,
                            causal=True, prefill_tiles=prefill_tiles,
                            ctx=ctx)
    x = ctx.p(x + a, "batch", "seq_sp", "embed")
    h = rmsnorm(x, shared["ln2"], cfg.norm_eps)
    x = x + mlp(shared["mlp"], h, cfg.mlp_act, ctx)
    return x, kv


def hybrid_forward(params: dict, tokens: jax.Array, cfg: ModelConfig, *,
                   remat: str = "none", return_cache: bool = False,
                   prefill_tiles: tuple[int, int] | None = None,
                   ctx: ShardCtx, chunk: int | None = None):
    ng, k = n_groups(cfg), cfg.hybrid_attn_every
    x = embed(params["embed"], tokens)
    x = ctx.p(x, "batch", "seq_sp", "embed")
    s = x.shape[1]
    cos, sin = rope_tables(jnp.arange(s), cfg.head_dim, cfg.rope_theta)
    flags = _group(_active_flags(cfg), ng, k)
    gblocks = _group(params["blocks"], ng, k)

    def group_body(x, xs):
        gp, gf = jax.lax.optimization_barrier(xs)
        x, kv = _shared_attn(params["shared"], x, cfg, cos, sin, ctx,
                             prefill_tiles=prefill_tiles)

        def layer_body(x, ls):
            lp, active = ls
            h = rmsnorm(x, lp["ln"], cfg.norm_eps)
            y = ssm_block(lp["ssm"], h, cfg, ctx, chunk=chunk)
            return ctx.p(x + jnp.where(active, y, 0), "batch", "seq_sp",
                         "embed"), None

        x, _ = jax.lax.scan(layer_body, x, (gp, gf))
        return x, (kv if return_cache else None)

    if remat == "full":
        group_body = jax.checkpoint(
            group_body, policy=jax.checkpoint_policies.nothing_saveable)
    elif remat == "dots":
        group_body = jax.checkpoint(
            group_body,
            policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    x, kvs = jax.lax.scan(group_body, x, (gblocks, flags))
    x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
    logits = unembed(params["embed"], x, ctx)
    if return_cache:
        return logits, jnp.float32(0.0), kvs
    return logits, jnp.float32(0.0)


# --------------------------------------------------------------------------- #
# Decode
# --------------------------------------------------------------------------- #


def hybrid_init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype,
                      abstract: bool = False, cache_dtype=None) -> dict:
    ng = n_groups(cfg)
    lp = padded_layers(cfg)
    g = max(cfg.num_kv_heads, 1)
    shapes = ssm_cache_shape(cfg, batch)
    # cache_dtype quantizes only the attention k/v (the paged pool);
    # the ssm state/conv stay at their recurrence dtypes
    kv_dt = jnp.dtype(cache_dtype) if cache_dtype is not None else dtype
    mk = (lambda s, dt: jax.ShapeDtypeStruct(s, dt)) if abstract else \
         (lambda s, dt: jnp.zeros(s, dt))
    return {
        "k": mk((ng, batch, max_len, g, cfg.head_dim), kv_dt),
        "v": mk((ng, batch, max_len, g, cfg.head_dim), kv_dt),
        "state": mk((lp,) + shapes["state"], jnp.float32),
        "conv": mk((lp,) + shapes["conv"], dtype),
        "pos": mk((), jnp.int32),
    }


def hybrid_decode(params: dict, cache: dict, tokens: jax.Array,
                  cfg: ModelConfig, *, ctx: ShardCtx,
                  decode_block=None, page_tables=None, page_block=None,
                  paged_decode_block=None):
    """One decode step.  ``cache["pos"]`` may be a scalar (fixed batch)
    or a (B,) vector (the serving pool's ragged rows); ``decode_block``
    is the bucket-tuned attention sweep mapping and ``page_tables``/
    ``page_block`` the physical block-table layout for the shared
    attention caches — with ``paged_decode_block`` the sweep consumes
    the tables directly (see ``attention.attention_decode``); the ssm
    states are position-free and never page."""
    ng, k = n_groups(cfg), cfg.hybrid_attn_every
    x = embed(params["embed"], tokens)
    pos = cache["pos"]
    rope_pos = pos[:, None] if pos.ndim else pos[None]
    cos, sin = rope_tables(rope_pos, cfg.head_dim, cfg.rope_theta)
    flags = _group(_active_flags(cfg), ng, k)
    gblocks = _group(params["blocks"], ng, k)
    gstate = _group(cache["state"], ng, k)
    gconv = _group(cache["conv"], ng, k)

    quant = "k_scale" in cache   # int8 paged pool: scales ride the scan

    def group_body(x, xs):
        if quant:
            gp, gf, kc, vc, ks, vs, st, cv = jax.lax.optimization_barrier(xs)
        else:
            gp, gf, kc, vc, st, cv = jax.lax.optimization_barrier(xs)
            ks = vs = None
        h = rmsnorm(x, params["shared"]["ln1"], cfg.norm_eps)
        a, kv = attention_decode(params["shared"]["attn"], h, cfg,
                                 kc, vc, pos, cos=cos, sin=sin,
                                 decode_block=decode_block,
                                 page_tables=page_tables,
                                 page_block=page_block,
                                 paged_decode_block=paged_decode_block,
                                 k_scale=ks, v_scale=vs, ctx=ctx)
        x = x + a
        h = rmsnorm(x, params["shared"]["ln2"], cfg.norm_eps)
        x = x + mlp(params["shared"]["mlp"], h, cfg.mlp_act, ctx)

        def layer_body(x, ls):
            lp, active, st_l, cv_l = ls
            h = rmsnorm(x, lp["ln"], cfg.norm_eps)
            y, st_n, cv_n = ssm_decode_step(lp["ssm"], h, st_l, cv_l, cfg, ctx)
            x = x + jnp.where(active, y, 0)
            return x, (st_n, cv_n)

        x, (st, cv) = jax.lax.scan(layer_body, x, (gp, gf, st, cv))
        return x, kv + (st, cv)

    xs = (gblocks, flags, cache["k"], cache["v"])
    if quant:
        xs = xs + (cache["k_scale"], cache["v_scale"])
    x, out = jax.lax.scan(group_body, x, xs + (gstate, gconv))
    st, cv = out[-2], out[-1]
    x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
    logits = unembed(params["embed"], x, ctx)
    lp = padded_layers(cfg)
    new_cache = {
        "k": out[0], "v": out[1],
        "state": st.reshape((lp,) + st.shape[2:]),
        "conv": cv.reshape((lp,) + cv.shape[2:]),
        "pos": pos + 1,
    }
    if quant:
        new_cache["k_scale"], new_cache["v_scale"] = out[2], out[3]
    return logits, new_cache
