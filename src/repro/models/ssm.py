"""Mamba-2 block (SSD — state-space duality, arXiv:2405.21060).

Training/prefill uses the chunked SSD form (``kernels.ref.ssd_chunked`` /
the Pallas path): quadratic within a chunk, linear across chunks — the
chunk length is the ``lws`` analogue (temporal loop per lane) resolved by
the runtime mapper.  Decode is the O(1) recurrent update on the carried
(H, N, P) state.

Layout: in_proj fans out to [z | x | B | C | dt]; depthwise causal conv
over [x | B | C]; per-head decay a = -exp(A_log)·dt; skip D·x; gated
RMSNorm before out_proj (Mamba-2's norm placement).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.hw import TpuParams
from repro.core.mapper import MappingPolicy, resolve_lws
from repro.models.layers import ParamSpec, ShardCtx, rmsnorm
from repro.kernels.ref import ssd_chunked


def plan_ssd_chunk(seq: int, hw: TpuParams | None = None,
                   policy: MappingPolicy = MappingPolicy.AUTO) -> int:
    """Chunk length = lws over time steps, tile-rounded, in [64, 512]."""
    if policy is MappingPolicy.NAIVE:
        return 64
    if policy is MappingPolicy.FIXED:
        return 256
    cores = hw.cores_per_chip if hw else 1
    lws = resolve_lws(seq, cores * 64)          # 64 pipeline slots per core
    c = max(64, min(512, 1 << max(6, (lws).bit_length())))
    while seq % c and c > 64:
        c //= 2
    return c


def ssm_specs(cfg: ModelConfig) -> dict:
    d, di = cfg.d_model, cfg.d_inner
    g, n, hh = cfg.ssm_groups, cfg.ssm_state, cfg.ssm_heads
    conv_ch = di + 2 * g * n
    return {
        "in_proj": ParamSpec((d, 2 * di + 2 * g * n + hh), ("embed", "inner")),
        "conv_w": ParamSpec((cfg.ssm_conv, conv_ch), ("conv", "inner")),
        "conv_b": ParamSpec((conv_ch,), ("inner",), init="zeros"),
        "a_log": ParamSpec((hh,), (None,), init="zeros"),
        "d_skip": ParamSpec((hh,), (None,), init="ones"),
        "dt_bias": ParamSpec((hh,), (None,), init="zeros"),
        "out_norm": ParamSpec((di,), ("inner",), init="zeros"),
        "out_proj": ParamSpec((di, d), ("inner", "embed")),
    }


def _split(proj, cfg: ModelConfig):
    di, g, n, hh = cfg.d_inner, cfg.ssm_groups, cfg.ssm_state, cfg.ssm_heads
    z = proj[..., :di]
    xbc = proj[..., di:di + di + 2 * g * n]
    dt = proj[..., di + di + 2 * g * n:]
    return z, xbc, dt


def _causal_conv(xbc: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv along time: xbc (B, S, C), w (K, C)."""
    k = w.shape[0]
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + xbc.shape[1]] * w[i] for i in range(k))
    return jax.nn.silu(out + b)


def ssm_block(params: dict, x: jax.Array, cfg: ModelConfig, ctx: ShardCtx,
              chunk: int | None = None, return_cache: bool = False):
    """x (B, S, d) -> (B, S, d).  Prefill/training path.

    With ``return_cache`` also returns (final ssm state, conv tail) so a
    prefill can seed the decode recurrence."""
    b, s, _ = x.shape
    di, g, n, hh, p = (cfg.d_inner, cfg.ssm_groups, cfg.ssm_state,
                       cfg.ssm_heads, cfg.ssm_head_dim)
    proj = jnp.einsum("bsd,de->bse", x, params["in_proj"])
    proj = ctx.p(proj, "batch", None, "inner")
    z, xbc_raw, dt_raw = _split(proj, cfg)
    xbc = _causal_conv(xbc_raw, params["conv_w"], params["conv_b"])
    xs = xbc[..., :di].reshape(b, s, hh, p)
    bs_ = xbc[..., di:di + g * n].reshape(b, s, g, n)
    cs = xbc[..., di + g * n:].reshape(b, s, g, n)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + params["dt_bias"].astype(jnp.float32))  # (B,S,H)
    a = -jnp.exp(params["a_log"].astype(jnp.float32)) * dt          # decay
    x_eff = xs.astype(jnp.float32) * dt[..., None]
    chunk = chunk or plan_ssd_chunk(s)
    chunk = min(chunk, s)
    while s % chunk:
        chunk //= 2
    y, state = jax.vmap(lambda xx, aa, bb, cc: ssd_chunked(
        xx, aa, bb, cc, chunk=chunk, return_state=True))(
        x_eff, a, bs_.astype(jnp.float32), cs.astype(jnp.float32))
    y = y + params["d_skip"].astype(jnp.float32)[None, None, :, None] * \
        xs.astype(jnp.float32)
    y = y.reshape(b, s, di).astype(x.dtype)
    y = rmsnorm(y * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype),
                params["out_norm"], cfg.norm_eps)
    out = jnp.einsum("bse,ed->bsd", y, params["out_proj"])
    if return_cache:
        # the decode conv window is the last K-1 inputs; prompts shorter
        # than that see pre-sequence zeros, matching _causal_conv's left pad
        tail = cfg.ssm_conv - 1
        pad = ((0, 0), (max(tail - s, 0), 0), (0, 0))
        conv_tail = jnp.pad(xbc_raw, pad)[:, -tail:, :].astype(x.dtype)
        return out, (state, conv_tail)
    return out


# --------------------------------------------------------------------------- #
# Decode (O(1) recurrence)
# --------------------------------------------------------------------------- #


def ssm_cache_shape(cfg: ModelConfig, batch: int):
    di, g, n = cfg.d_inner, cfg.ssm_groups, cfg.ssm_state
    conv_ch = di + 2 * g * n
    return {
        "state": (batch, cfg.ssm_heads, n, cfg.ssm_head_dim),
        "conv": (batch, cfg.ssm_conv - 1, conv_ch),
    }


def ssm_decode_step(params: dict, x: jax.Array, state: jax.Array,
                    conv_state: jax.Array, cfg: ModelConfig, ctx: ShardCtx):
    """x (B, 1, d); state (B, H, N, P); conv_state (B, K-1, C)."""
    b = x.shape[0]
    di, g, n, hh, p = (cfg.d_inner, cfg.ssm_groups, cfg.ssm_state,
                       cfg.ssm_heads, cfg.ssm_head_dim)
    proj = jnp.einsum("bsd,de->bse", x, params["in_proj"])
    z, xbc, dt_raw = _split(proj, cfg)
    xbc1 = xbc[:, 0]                                        # (B, C)
    # roll conv state
    window = jnp.concatenate([conv_state, xbc1[:, None, :]], axis=1)  # (B,K,C)
    conv_out = jnp.einsum("bkc,kc->bc", window, params["conv_w"]) \
        + params["conv_b"]
    conv_out = jax.nn.silu(conv_out)
    new_conv = window[:, 1:]
    xs = conv_out[..., :di].reshape(b, hh, p)
    bs_ = conv_out[..., di:di + g * n].reshape(b, g, n)
    cs = conv_out[..., di + g * n:].reshape(b, g, n)
    rep = hh // g
    bh = jnp.repeat(bs_, rep, axis=1)                       # (B, H, N)
    ch = jnp.repeat(cs, rep, axis=1)
    dt = jax.nn.softplus(dt_raw[:, 0].astype(jnp.float32)
                         + params["dt_bias"].astype(jnp.float32))  # (B, H)
    a = jnp.exp(-jnp.exp(params["a_log"].astype(jnp.float32)) * dt)
    x_eff = xs.astype(jnp.float32) * dt[..., None]
    state = state * a[..., None, None] + jnp.einsum(
        "bhn,bhp->bhnp", bh.astype(jnp.float32), x_eff)
    y = jnp.einsum("bhn,bhnp->bhp", ch.astype(jnp.float32), state)
    y = y + params["d_skip"].astype(jnp.float32)[None, :, None] * \
        xs.astype(jnp.float32)
    y = y.reshape(b, di).astype(x.dtype)
    y = rmsnorm(y * jax.nn.silu(z[:, 0].astype(jnp.float32)).astype(x.dtype),
                params["out_norm"], cfg.norm_eps)
    out = jnp.einsum("be,ed->bd", y, params["out_proj"])
    return out[:, None, :], state, new_conv


# --------------------------------------------------------------------------- #
# Full attention-free LM stack (mamba2-1.3b)
# --------------------------------------------------------------------------- #

from repro.models.layers import (embed, embed_specs, stack_specs,  # noqa: E402
                                 unembed)


def ssm_block_specs(cfg: ModelConfig) -> dict:
    return {
        "ln": ParamSpec((cfg.d_model,), ("embed",), init="zeros"),
        "ssm": ssm_specs(cfg),
    }


def ssm_model_specs(cfg: ModelConfig) -> dict:
    return {
        "embed": embed_specs(cfg),
        "blocks": stack_specs(ssm_block_specs(cfg), cfg.num_layers),
        "ln_f": ParamSpec((cfg.d_model,), ("embed",), init="zeros"),
    }


def ssm_forward(params: dict, tokens: jax.Array, cfg: ModelConfig, *,
                remat: str = "none", return_cache: bool = False,
                ctx: ShardCtx, chunk: int | None = None):
    x = embed(params["embed"], tokens)
    x = ctx.p(x, "batch", "seq_sp", "embed")

    def body(x, layer_params):
        layer_params = jax.lax.optimization_barrier(layer_params)
        h = rmsnorm(x, layer_params["ln"], cfg.norm_eps)
        if return_cache:
            y, cache = ssm_block(layer_params["ssm"], h, cfg, ctx,
                                 chunk=chunk, return_cache=True)
        else:
            y, cache = ssm_block(layer_params["ssm"], h, cfg, ctx,
                                 chunk=chunk), None
        return ctx.p(x + y, "batch", "seq_sp", "embed"), cache

    if remat == "full":
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    elif remat == "dots":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    x, caches = jax.lax.scan(body, x, params["blocks"])
    x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
    logits = unembed(params["embed"], x, ctx)
    if return_cache:
        return logits, jnp.float32(0.0), caches
    return logits, jnp.float32(0.0)


def ssm_init_cache(cfg: ModelConfig, batch: int, dtype, abstract=False):
    shapes = ssm_cache_shape(cfg, batch)
    mk = (lambda s, dt: jax.ShapeDtypeStruct(s, dt)) if abstract else \
         (lambda s, dt: jnp.zeros(s, dt))
    return {
        "state": mk((cfg.num_layers,) + shapes["state"], jnp.float32),
        "conv": mk((cfg.num_layers,) + shapes["conv"], dtype),
        "pos": mk((), jnp.int32),
    }


def ssm_decode(params: dict, cache: dict, tokens: jax.Array,
               cfg: ModelConfig, *, ctx: ShardCtx,
               decode_block=None, page_tables=None, page_block=None,
               paged_decode_block=None):
    """One recurrent decode step.  The state update is position-free, so
    a vector ``cache["pos"]`` (the serving pool's ragged rows) needs no
    special handling — it only advances per row.  ``decode_block`` and
    the ``page_*`` arguments are accepted for decode-step API
    uniformity and ignored: there is no attention sweep to map and no
    time axis to page (the family is attention-free; under physical
    paging its pool participates in block *accounting* only)."""
    del decode_block, page_tables, page_block, paged_decode_block
    x = embed(params["embed"], tokens)

    def body(x, xs):
        layer_params, st, cv = jax.lax.optimization_barrier(xs)
        h = rmsnorm(x, layer_params["ln"], cfg.norm_eps)
        y, st, cv = ssm_decode_step(layer_params["ssm"], h, st, cv, cfg, ctx)
        return x + y, (st, cv)

    x, (st, cv) = jax.lax.scan(body, x,
                               (params["blocks"], cache["state"], cache["conv"]))
    x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
    logits = unembed(params["embed"], x, ctx)
    return logits, {"state": st, "conv": cv, "pos": cache["pos"] + 1}
