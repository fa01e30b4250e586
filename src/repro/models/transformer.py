"""Decoder-only transformer stack (dense + MoE + VLM prefix variants).

Layers are stacked on a leading axis and iterated with ``jax.lax.scan`` so
the lowered HLO is O(1) in depth (essential for the 512-device dry-run
compiles) with selectable per-layer remat.

gemma3's 5:1 local:global pattern is expressed as a per-layer flag vector
scanned alongside the stacked params; local layers use sliding-window
masks and the local rope theta (10k) while global layers use the long
theta — both rope tables are precomputed once and selected per layer.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import moe as moe_mod
from repro.models.attention import (_cache_read, _cache_write,
                                    _chunk_cache_write, _pallas_mode,
                                    attention_block, attention_decode,
                                    attention_specs, _project_qkv,
                                    pallas_prefill_attention,
                                    tiled_prefill_attention)
from repro.models.layers import (NO_SHARD, ParamSpec, ShardCtx, embed,
                                 embed_specs, mlp, mlp_specs, rmsnorm,
                                 rope_tables, stack_specs, unembed)

LOCAL_ROPE_THETA = 10_000.0


def block_specs(cfg: ModelConfig) -> dict:
    s = {
        "ln1": ParamSpec((cfg.d_model,), ("embed",), init="zeros"),
        "attn": attention_specs(cfg),
        "ln2": ParamSpec((cfg.d_model,), ("embed",), init="zeros"),
    }
    if cfg.family == "moe":
        s["moe"] = moe_mod.moe_specs(cfg)
    else:
        s["mlp"] = mlp_specs(cfg)
    return s


def model_specs(cfg: ModelConfig) -> dict:
    return {
        "embed": embed_specs(cfg),
        "blocks": stack_specs(block_specs(cfg), cfg.num_layers),
        "ln_f": ParamSpec((cfg.d_model,), ("embed",), init="zeros"),
    }


def layer_flags(cfg: ModelConfig) -> jax.Array:
    """(L,) bool — True where the layer uses GLOBAL attention."""
    if cfg.local_global_ratio:
        idx = jnp.arange(cfg.num_layers)
        return (idx % (cfg.local_global_ratio + 1)) == cfg.local_global_ratio
    return jnp.ones((cfg.num_layers,), bool)


def _mlp_or_moe(layer_params, cfg, h, ctx, dropless=False, valid=None):
    """(output, aux): the load-balance loss of training's capacity layer,
    or with ``dropless`` (serving) the layer's load on each held expert
    (``moe.moe_dropless``) over the tokens ``valid`` marks."""
    if cfg.family == "moe":
        if dropless:
            return moe_mod.moe_dropless(layer_params["moe"], h, cfg, valid)
        return moe_mod.moe_mlp(layer_params["moe"], h, cfg, ctx)
    return mlp(layer_params["mlp"], h, cfg.mlp_act, ctx), 0.0


def _layer_window(cfg: ModelConfig, is_global):
    """Per-layer sliding window; dynamic (traced) for local:global mixes.

    The mask predicate ``k_pos > q_pos - window`` accepts a traced window,
    so gemma3's 5:1 pattern costs ONE attention per layer (the global
    layers just get an effectively-infinite window)."""
    if not cfg.window:
        return None
    if cfg.local_global_ratio:
        return jnp.where(is_global, jnp.int32(2 ** 30), jnp.int32(cfg.window))
    return cfg.window


def _block_fwd(layer_params, x, cfg: ModelConfig, *, is_global, cos_l, sin_l,
               cos_g, sin_g, prefix_len, q_offset, kv_override=None,
               causal=True, prefill_tiles=None, dropless=False,
               ctx: ShardCtx):
    cos = jnp.where(is_global, cos_g, cos_l) if cfg.local_global_ratio else cos_g
    sin = jnp.where(is_global, sin_g, sin_l) if cfg.local_global_ratio else sin_g
    h = rmsnorm(x, layer_params["ln1"], cfg.norm_eps)
    a, kv = attention_block(
        layer_params["attn"], h, cfg, cos=cos, sin=sin, causal=causal,
        window=_layer_window(cfg, is_global), prefix_len=prefix_len,
        q_offset=q_offset, kv_override=kv_override,
        prefill_tiles=prefill_tiles, ctx=ctx)
    x = ctx.p(x + a, "batch", "seq_sp", "embed")
    h = rmsnorm(x, layer_params["ln2"], cfg.norm_eps)
    m, aux = _mlp_or_moe(layer_params, cfg, h, ctx, dropless)
    x = ctx.p(x + m, "batch", "seq_sp", "embed")
    return x, kv, (0.0 if dropless else aux)


def forward(
    params: dict,
    tokens: jax.Array,                    # (B, S) int32
    cfg: ModelConfig,
    *,
    prefix_embeds: Optional[jax.Array] = None,   # (B, P, D) VLM stub
    remat: str = "none",                  # none | full | dots
    return_cache: bool = False,
    prefill_tiles: Optional[tuple[int, int]] = None,
    dropless: bool = False,
    ctx: ShardCtx = NO_SHARD,
):
    """Training/prefill forward.  Returns (logits, aux_loss[, kv caches]).

    ``prefill_tiles`` — the serving router's bucket-tuned flash
    (block_q, block_k) — makes every layer's attention EXECUTE at that
    mapping (see ``attention.attention_block``); ``None`` keeps the
    GSPMD path.  ``dropless`` (serving's prefill) runs the expert layers
    without capacity (``moe.moe_dropless``); the aux loss is then 0."""
    if (ctx.flag("banded_local", False) and cfg.local_global_ratio
            and cfg.window and prefix_embeds is None):
        return forward_banded(params, tokens, cfg, remat=remat,
                              return_cache=return_cache, ctx=ctx)
    x = embed(params["embed"], tokens)
    prefix_len = None
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
        prefix_len = prefix_embeds.shape[1]
    b, s, _ = x.shape
    x = ctx.p(x, "batch", "seq_sp", "embed")
    pos = jnp.arange(s)
    cos_g, sin_g = rope_tables(pos, cfg.head_dim, cfg.rope_theta)
    cos_l, sin_l = rope_tables(pos, cfg.head_dim, LOCAL_ROPE_THETA)
    flags = layer_flags(cfg)

    def body(carry, xs):
        x, aux = carry
        # barrier: keep per-layer converts inside the loop (see optim.adamw)
        layer_params, is_global = jax.lax.optimization_barrier(xs)
        x, kv, a = _block_fwd(layer_params, x, cfg, is_global=is_global,
                              cos_l=cos_l, sin_l=sin_l, cos_g=cos_g,
                              sin_g=sin_g, prefix_len=prefix_len,
                              q_offset=0, prefill_tiles=prefill_tiles,
                              dropless=dropless, ctx=ctx)
        return (x, aux + a), (kv if return_cache else None)

    if remat == "full":
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    elif remat == "dots":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    elif remat == "moe":
        # save only the post-all-to-all expert buffers: the recompute pass
        # skips the dispatch/combine collectives (see EXPERIMENTS.md §Perf)
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.save_only_these_names(
                "moe_in"))

    (x, aux), caches = jax.lax.scan(body, (x, 0.0), (params["blocks"], flags))
    x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
    logits = unembed(params["embed"], x, ctx)
    if return_cache:
        return logits, aux, caches
    return logits, aux


def forward_banded(
    params: dict,
    tokens: jax.Array,
    cfg: ModelConfig,
    *,
    remat: str = "none",
    return_cache: bool = False,
    ctx: ShardCtx = NO_SHARD,
):
    """§Perf variant for local:global archs (gemma3): layers regrouped
    STATICALLY into (ratio local + 1 global) groups so the local layers use
    exact O(S·window) banded attention instead of the masked full sweep.

    Identical math to ``forward`` (tests pin it); only the schedule — the
    lws-style mapping of attention work onto blocks — changes."""
    ratio = cfg.local_global_ratio
    gsz = ratio + 1
    n_full = cfg.num_layers // gsz
    tail = cfg.num_layers - n_full * gsz           # trailing local layers
    x = embed(params["embed"], tokens)
    b, s, _ = x.shape
    x = ctx.p(x, "batch", "seq_sp", "embed")
    pos = jnp.arange(s)
    cos_g, sin_g = rope_tables(pos, cfg.head_dim, cfg.rope_theta)
    cos_l, sin_l = rope_tables(pos, cfg.head_dim, LOCAL_ROPE_THETA)

    def local_block(lp, x):
        h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
        a, kv = attention_block(lp["attn"], h, cfg, cos=cos_l, sin=sin_l,
                                window=cfg.window, banded=True, ctx=ctx)
        x = ctx.p(x + a, "batch", "seq_sp", "embed")
        h = rmsnorm(x, lp["ln2"], cfg.norm_eps)
        m, _ = _mlp_or_moe(lp, cfg, h, ctx)
        return ctx.p(x + m, "batch", "seq_sp", "embed"), kv

    def global_block(lp, x):
        h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
        a, kv = attention_block(lp["attn"], h, cfg, cos=cos_g, sin=sin_g,
                                window=None, ctx=ctx)
        x = ctx.p(x + a, "batch", "seq_sp", "embed")
        h = rmsnorm(x, lp["ln2"], cfg.norm_eps)
        m, _ = _mlp_or_moe(lp, cfg, h, ctx)
        return ctx.p(x + m, "batch", "seq_sp", "embed"), kv

    grouped = jax.tree.map(
        lambda a: a[:n_full * gsz].reshape((n_full, gsz) + a.shape[1:]),
        params["blocks"])
    tailp = jax.tree.map(lambda a: a[n_full * gsz:], params["blocks"])

    def group_body(x, gp):
        gp = jax.lax.optimization_barrier(gp)
        loc = jax.tree.map(lambda a: a[:ratio], gp)
        glob = jax.tree.map(lambda a: a[ratio], gp)
        x, kvs_l = jax.lax.scan(lambda xx, lp: local_block(lp, xx), x, loc)
        x, kv_g = global_block(glob, x)
        return x, ((kvs_l, kv_g) if return_cache else None)

    if remat == "full":
        group_body = jax.checkpoint(
            group_body, policy=jax.checkpoint_policies.nothing_saveable)
    x, gcaches = jax.lax.scan(group_body, x, grouped)

    def tail_body(x, lp):
        lp = jax.lax.optimization_barrier(lp)
        x, kv = local_block(lp, x)
        return x, (kv if return_cache else None)

    if tail:
        x, tcaches = jax.lax.scan(tail_body, x, tailp)
    x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
    logits = unembed(params["embed"], x, ctx)
    if not return_cache:
        return logits, jnp.float32(0.0)
    # reassemble caches into layer order (L, B, S, G, hd)
    (kl, vl), (kg, vg) = gcaches

    def weave(loc, glob, tail_c):
        full = jnp.concatenate([loc, glob[:, None]], axis=1)
        full = full.reshape((n_full * gsz,) + full.shape[2:])
        return jnp.concatenate([full, tail_c], 0) if tail else full

    k = weave(kl, kg, tcaches[0] if tail else None)
    v = weave(vl, vg, tcaches[1] if tail else None)
    return logits, jnp.float32(0.0), (k, v)


# --------------------------------------------------------------------------- #
# Decode path
# --------------------------------------------------------------------------- #


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype,
               expand_kv: bool = False) -> dict:
    g = cfg.num_heads if expand_kv else max(cfg.num_kv_heads, 1)
    shape = (cfg.num_layers, batch, max_len, g, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "pos": jnp.zeros((), jnp.int32),
    }


def abstract_cache(cfg: ModelConfig, batch: int, max_len: int, dtype,
                   expand_kv: bool = False) -> dict:
    g = cfg.num_heads if expand_kv else max(cfg.num_kv_heads, 1)
    shape = (cfg.num_layers, batch, max_len, g, cfg.head_dim)
    return {
        "k": jax.ShapeDtypeStruct(shape, dtype),
        "v": jax.ShapeDtypeStruct(shape, dtype),
        "pos": jax.ShapeDtypeStruct((), jnp.int32),
    }


def chunk_prefill_step(
    params: dict,
    cache: dict,
    tokens: jax.Array,                    # (B, C) — one prompt chunk
    cfg: ModelConfig,
    *,
    n_valid=None,
    prefill_tiles: Optional[tuple[int, int]] = None,
    ctx: ShardCtx = NO_SHARD,
):
    """Advance a prefill cache by one C-token prompt chunk.

    The chunk's queries attend over the growing cache (everything written
    by earlier chunks plus this chunk's own keys) through the same
    tiles the whole-prompt prefill executes — the Pallas flash kernel on
    the kernel path, ``tiled_prefill_attention`` otherwise — with
    ``q_offset = cache["pos"]`` kept
    TRACED — one compilation serves every chunk of every prompt at a
    given (chunk, cache_len) shape.  The chunk's k/v land in the cache at
    positions ``pos .. pos+C-1`` via the same positional write the decode
    path uses.

    Tail chunks may carry right-padding: padded queries compute garbage
    rows that the caller discards (per-query attention is independent),
    and the garbage k/v they write sit at positions ``>= prompt_len``
    that causal masking hides from every valid query — the serving
    engine's ``write_row`` then copies only real positions into the
    pool.  No validity mask is needed inside the step.

    Returns (logits (B, C, V), updated cache), and for a config with
    experts also the dropless layer's load (L, held) int32: its routed
    pairs on each held expert, of the chunk's first ``n_valid`` (traced;
    default all) positions — padding adds no expert work.  The caller
    reads the true last-token logits at index ``n_valid - 1`` of the
    final chunk.
    """
    b, c = tokens.shape
    x = embed(params["embed"], tokens)
    x = ctx.p(x, "batch", None, "embed")
    start = cache["pos"]                                  # scalar, traced
    pos = start + jnp.arange(c)
    cos_g, sin_g = rope_tables(pos, cfg.head_dim, cfg.rope_theta)
    cos_l, sin_l = rope_tables(pos, cfg.head_dim, LOCAL_ROPE_THETA)
    flags = layer_flags(cfg)
    # default tiles: one query tile over the chunk, keys swept in 512s —
    # the untiled reference schedule (serving always passes tuned tiles)
    bq, bk = prefill_tiles if prefill_tiles is not None else (c, 512)
    use_pallas, interpret = _pallas_mode()
    experts = cfg.family == "moe"
    valid = None
    if experts and n_valid is not None:
        valid = jnp.broadcast_to(jnp.arange(c) < n_valid, (b, c))

    def body(x, xs):
        layer_params, is_global, k_c, v_c = jax.lax.optimization_barrier(xs)
        cos = jnp.where(is_global, cos_g, cos_l) if cfg.local_global_ratio else cos_g
        sin = jnp.where(is_global, sin_g, sin_l) if cfg.local_global_ratio else sin_g
        h = rmsnorm(x, layer_params["ln1"], cfg.norm_eps)
        q, k, v = _project_qkv(layer_params["attn"], h, cfg, cos, sin, ctx)
        # scatter, not dynamic_update_slice: a radix-resumed chunk's
        # start is not chunk-aligned, and the slab's overhang must DROP
        # rather than clamp-clobber the seeded prefix rows
        k_c = _chunk_cache_write(k_c, k, start)
        v_c = _chunk_cache_write(v_c, v, start)
        kr, vr = _cache_read(k_c, x.dtype), _cache_read(v_c, x.dtype)
        win = _layer_window(cfg, is_global)
        if use_pallas and win is None:
            o = pallas_prefill_attention(q, kr, vr, block_q=bq, block_k=bk,
                                         q_offset=start, interpret=interpret)
        else:
            # sliding windows stay on the reference sweep (the kernel
            # masks causality only), which honours the same tiles
            o = tiled_prefill_attention(q, kr, vr, block_q=bq, block_k=bk,
                                        causal=True, window=win,
                                        q_offset=start)
        a = jnp.einsum("bshk,hkd->bsd", o.reshape(b, c, -1, cfg.head_dim),
                       layer_params["attn"]["wo"])
        x = x + a
        h = rmsnorm(x, layer_params["ln2"], cfg.norm_eps)
        m, load = _mlp_or_moe(layer_params, cfg, h, ctx, True, valid)
        return x + m, ((k_c, v_c, load) if experts else (k_c, v_c))

    x, ys = jax.lax.scan(
        body, x, (params["blocks"], flags, cache["k"], cache["v"]))
    x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
    logits = unembed(params["embed"], x, ctx)
    new_cache = {"k": ys[0], "v": ys[1], "pos": start + c}
    if experts:
        return logits, new_cache, ys[2]
    return logits, new_cache


def decode_step(
    params: dict,
    cache: dict,
    tokens: jax.Array,                    # (B, 1)
    cfg: ModelConfig,
    *,
    ctx: ShardCtx = NO_SHARD,
    decode_block: Optional[int] = None,
    page_tables=None,
    page_block: Optional[int] = None,
    paged_decode_block: Optional[int] = None,
    rows=None,
):
    """One greedy decode step: (logits (B,1,V), updated cache), and for a
    config with experts also the dropless layer's load (L, held) int32:
    its routed pairs on each held expert, of the rows ``rows`` (B,) bool
    marks in flight (default all) — a row not in flight adds no expert
    work.

    ``cache["pos"]`` may be a scalar (every row at the same depth — the
    fixed-batch loop) or a (B,) vector (the serving pool's ragged rows:
    per-row rope positions, cache writes, and length masks).

    ``decode_block`` — the bucket-tuned cache block from the serving
    router — selects the executed attention sweep (see
    ``attention.attention_decode``); ``None`` keeps the einsum path.
    ``page_tables``/``page_block`` switch the KV arrays to the physical
    block-table layout (scatter writes, gather-by-table reads);
    ``paged_decode_block`` additionally fuses the read — the sweep
    consumes the tables directly instead of gathering first."""
    x = embed(params["embed"], tokens)
    x = ctx.p(x, "batch", None, "embed")
    pos = cache["pos"]
    rope_pos = pos[:, None] if pos.ndim else pos[None]
    cos_g, sin_g = rope_tables(rope_pos, cfg.head_dim, cfg.rope_theta)
    cos_l, sin_l = rope_tables(rope_pos, cfg.head_dim, LOCAL_ROPE_THETA)
    flags = layer_flags(cfg)

    # the quantized paged pool threads per-(block, head) scales through
    # the layer scan; the branch is PYTHON-level (a dict-key check), so
    # the unquantized trace stays byte-identical to the pre-int8 graph
    quant = "k_scale" in cache
    experts = cfg.family == "moe"

    def body(x, xs):
        if quant:
            layer_params, is_global, k_c, v_c, ks, vs = \
                jax.lax.optimization_barrier(xs)
        else:
            layer_params, is_global, k_c, v_c = \
                jax.lax.optimization_barrier(xs)
            ks = vs = None
        cos = jnp.where(is_global, cos_g, cos_l) if cfg.local_global_ratio else cos_g
        sin = jnp.where(is_global, sin_g, sin_l) if cfg.local_global_ratio else sin_g
        h = rmsnorm(x, layer_params["ln1"], cfg.norm_eps)
        win = _layer_window(cfg, is_global)
        a, kv = attention_decode(
            layer_params["attn"], h, cfg, k_c, v_c, pos,
            cos=cos, sin=sin, window=win, decode_block=decode_block,
            page_tables=page_tables, page_block=page_block,
            paged_decode_block=paged_decode_block,
            k_scale=ks, v_scale=vs, ctx=ctx)
        x = x + a
        h = rmsnorm(x, layer_params["ln2"], cfg.norm_eps)
        m, load = _mlp_or_moe(layer_params, cfg, h, ctx, True, rows)
        return x + m, ((kv, load) if experts else kv)

    xs = (params["blocks"], flags, cache["k"], cache["v"])
    if quant:
        xs = xs + (cache["k_scale"], cache["v_scale"])
    x, ys = jax.lax.scan(body, x, xs)
    kv_new = ys[0] if experts else ys
    x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
    logits = unembed(params["embed"], x, ctx)
    new_cache = {"k": kv_new[0], "v": kv_new[1], "pos": pos + 1}
    if quant:
        new_cache["k_scale"], new_cache["v_scale"] = kv_new[2], kv_new[3]
    if experts:
        return logits, new_cache, ys[1]
    return logits, new_cache
