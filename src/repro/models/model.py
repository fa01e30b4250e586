"""Unified model facade — one API over all 6 families.

Everything the launch layer needs:

  m = build_model(get_config("qwen3-8b"))
  params = m.init(rng)                       # or m.abstract_params()
  loss, aux = m.loss(params, batch, ctx=ctx)
  logits, cache = m.prefill(params, batch, max_len, ctx=ctx)
  logits, cache = m.decode_step(params, cache, tokens, ctx=ctx)
  # moe serving steps also return the expert layer's load (L, held)

``input_specs`` builds the allocation-free ShapeDtypeStruct stand-ins for
every (shape x kind) cell, including the stub modality inputs (VLM patch
embeddings / whisper frame embeddings) per the assignment.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import encdec as encdec_mod
from repro.models import hybrid as hybrid_mod
from repro.models import ssm as ssm_mod
from repro.models import transformer as tf_mod
from repro.models.layers import (NO_SHARD, ShardCtx, abstract_params,
                                 init_params, param_count, spec_axes)

PyTree = Any


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask: jax.Array) -> jax.Array:
    """Masked token-mean CE in f32."""
    lf = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(lf, axis=-1)
    ll = jnp.take_along_axis(lf, labels[..., None].astype(jnp.int32),
                             axis=-1)[..., 0]
    nll = (logz - ll) * mask
    return nll.sum() / jnp.maximum(mask.sum(), 1.0)


@dataclasses.dataclass
class Model:
    cfg: ModelConfig
    aux_weight: float = 0.01     # MoE load-balance weight

    # ------------------------------------------------------------------ #
    @property
    def dtype(self):
        return jnp.dtype(self.cfg.dtype)

    @functools.cached_property
    def specs(self) -> PyTree:
        f = self.cfg.family
        if f in ("dense", "moe", "vlm"):
            return tf_mod.model_specs(self.cfg)
        if f == "ssm":
            return ssm_mod.ssm_model_specs(self.cfg)
        if f == "hybrid":
            return hybrid_mod.hybrid_model_specs(self.cfg)
        if f == "encdec":
            return encdec_mod.encdec_model_specs(self.cfg)
        raise ValueError(f"unknown family {f!r}")

    @functools.cached_property
    def logical_axes(self) -> PyTree:
        return spec_axes(self.specs)

    def init(self, rng: jax.Array) -> PyTree:
        return init_params(self.specs, rng, self.dtype)

    def abstract_params(self) -> PyTree:
        return abstract_params(self.specs, self.dtype)

    def param_count(self) -> int:
        return param_count(self.specs)

    # ------------------------------------------------------------------ #
    def forward(self, params, batch: dict, *, remat: str = "none",
                return_cache: bool = False,
                prefill_tiles: Optional[tuple[int, int]] = None,
                ctx: ShardCtx = NO_SHARD):
        """Family-dispatched forward.  ``prefill_tiles`` — the serving
        router's bucket-tuned flash (block_q, block_k) — parameterizes
        the EXECUTED attention mapping for the attention families;
        ``None`` (and the attention-free ssm family) keeps the
        hardware-agnostic GSPMD path byte-for-byte.  A forward that
        returns its cache is serving's prefill: its expert layers run
        dropless (``moe.moe_dropless``)."""
        cfg, f = self.cfg, self.cfg.family
        tokens = batch["tokens"]
        if f in ("dense", "moe"):
            return tf_mod.forward(params, tokens, cfg, remat=remat,
                                  return_cache=return_cache,
                                  prefill_tiles=prefill_tiles,
                                  dropless=return_cache, ctx=ctx)
        if f == "vlm":
            return tf_mod.forward(params, tokens, cfg, remat=remat,
                                  prefix_embeds=batch["patches"],
                                  return_cache=return_cache,
                                  prefill_tiles=prefill_tiles, ctx=ctx)
        if f == "ssm":
            return ssm_mod.ssm_forward(params, tokens, cfg, remat=remat,
                                       return_cache=return_cache, ctx=ctx)
        if f == "hybrid":
            return hybrid_mod.hybrid_forward(params, tokens, cfg, remat=remat,
                                             return_cache=return_cache,
                                             prefill_tiles=prefill_tiles,
                                             ctx=ctx)
        if f == "encdec":
            return encdec_mod.encdec_forward(params, tokens, batch["frames"],
                                             cfg, remat=remat,
                                             return_cache=return_cache,
                                             prefill_tiles=prefill_tiles,
                                             ctx=ctx)
        raise ValueError(f)

    def loss(self, params, batch: dict, *, remat: str = "none",
             ctx: ShardCtx = NO_SHARD):
        out = self.forward(params, batch, remat=remat, ctx=ctx)
        logits, aux = out[0], out[1]
        labels, mask = batch["labels"], batch["mask"]
        if self.cfg.family == "vlm":
            # loss only over the text suffix
            p = self.cfg.prefix_tokens
            logits = logits[:, p:]
        ce = cross_entropy(logits[:, :-1], labels[:, 1:], mask[:, 1:])
        return ce + self.aux_weight * aux, {"ce": ce, "aux": aux}

    # ------------------------------------------------------------------ #
    # Serving
    # ------------------------------------------------------------------ #
    def init_cache(self, batch: int, max_len: int, abstract: bool = False,
                   expand_kv: bool = False, cache_dtype=None):
        cfg, f = self.cfg, self.cfg.family
        cdt = jnp.dtype(cache_dtype) if cache_dtype else self.dtype
        if f in ("dense", "moe", "vlm"):
            if abstract:
                return tf_mod.abstract_cache(cfg, batch, max_len, cdt,
                                             expand_kv=expand_kv)
            return tf_mod.init_cache(cfg, batch, max_len, cdt,
                                     expand_kv=expand_kv)
        if f == "ssm":
            return ssm_mod.ssm_init_cache(cfg, batch, self.dtype,
                                          abstract=abstract)
        if f == "hybrid":
            return hybrid_mod.hybrid_init_cache(cfg, batch, max_len,
                                                self.dtype, abstract=abstract,
                                                cache_dtype=cache_dtype)
        if f == "encdec":
            return encdec_mod.encdec_init_cache(cfg, batch, max_len,
                                                self.dtype, abstract=abstract,
                                                cache_dtype=cache_dtype)
        raise ValueError(f)

    def prefill(self, params, batch: dict, max_len: int, *,
                last_pos=None,
                prefill_tiles: Optional[tuple[int, int]] = None,
                ctx: ShardCtx = NO_SHARD):
        """Run the prompt, return (last-token logits, primed cache).

        ``last_pos`` (B,) selects each row's TRUE final-token logits when
        prompts are right-padded to a shape bucket (the serving engine's
        admission path); ``None`` keeps the fixed-batch behaviour of
        reading position -1.

        ``prefill_tiles`` is the bucket-tuned flash (block_q, block_k)
        from ``serve.buckets.BucketRouter.prefill_tiles``: the attention
        sweep EXECUTES at that mapping (Pallas flash kernel where
        available, tile-honouring blocked reference elsewhere); ``None``
        keeps the GSPMD path for non-serving callers."""
        cfg, f = self.cfg, self.cfg.family
        tokens = batch["tokens"]
        b, s = tokens.shape
        out = self.forward(params, batch, return_cache=True,
                           prefill_tiles=prefill_tiles, ctx=ctx)
        logits, _, caches = out
        if f in ("dense", "moe", "vlm"):
            k, v = caches                       # (L, B, S', G, hd)
            pad = max_len - k.shape[2]
            k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
            cache = {"k": k.astype(self.dtype), "v": v.astype(self.dtype),
                     "pos": jnp.int32(k.shape[2] - pad)}
        elif f == "ssm":
            state, conv = caches
            cache = {"state": state, "conv": conv.astype(self.dtype),
                     "pos": jnp.int32(s)}
        elif f == "hybrid":
            k, v = caches
            pad = max_len - k.shape[2]
            k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
            # hybrid prefill seeds attention caches; ssm states re-derived
            # per group in hybrid_forward(return_cache) — simplified: zeros
            base = hybrid_mod.hybrid_init_cache(cfg, b, max_len, self.dtype)
            cache = dict(base, k=k.astype(self.dtype), v=v.astype(self.dtype),
                         pos=jnp.int32(s))
        elif f == "encdec":
            (kv, ckv) = caches
            k, v = kv
            ck, cv = ckv
            pad = max_len - k.shape[2]
            k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
            cache = {"k": k.astype(self.dtype), "v": v.astype(self.dtype),
                     "ck": ck.astype(self.dtype), "cv": cv.astype(self.dtype),
                     "pos": jnp.int32(s)}
        else:
            raise ValueError(f)
        if last_pos is not None:
            idx = jnp.asarray(last_pos, jnp.int32)[:, None, None]
            return jnp.take_along_axis(logits, idx, axis=1), cache
        return logits[:, -1:], cache

    @property
    def supports_chunked_prefill(self) -> bool:
        """Families servable through ``prefill_chunk``.  encdec is out:
        its cross-KV cache needs the whole encoder pass up front; vlm
        needs the prefix embeddings concatenated before position 0."""
        return self.cfg.family in ("dense", "moe", "ssm", "hybrid")

    def prefill_chunk(self, params, cache, tokens, n_valid, *,
                      prefill_tiles: Optional[tuple[int, int]] = None,
                      ctx: ShardCtx = NO_SHARD):
        """Advance a prefill cache by one (B, C) prompt chunk.

        Attention families run a true multi-token chunk step: the
        chunk's queries sweep the growing cache at the bucket-tuned
        tiles with a traced start offset, so ONE compilation serves
        every chunk of every prompt at a given (C, cache_len) shape
        (``transformer.chunk_prefill_step``).  Recurrent families (ssm,
        hybrid) scan their own decode step over the chunk tokens — the
        exact sequential recurrence — with steps ``>= n_valid`` masked
        out, which bounds their prefill compile set to ONE shape per
        chunk size instead of one per distinct prompt length.

        ``n_valid`` (traced scalar) is the number of real tokens in the
        chunk; only tail chunks carry padding.  Returns
        (logits (B, C, V), updated cache) — the caller reads the true
        last-token logits at ``[:, n_valid - 1]`` of the final chunk —
        and for moe also the expert layer's load (L, held) over the
        ``n_valid`` real tokens.
        """
        cfg, f = self.cfg, self.cfg.family
        if f in ("dense", "moe"):
            return tf_mod.chunk_prefill_step(params, cache, tokens, cfg,
                                             n_valid=n_valid,
                                             prefill_tiles=prefill_tiles,
                                             ctx=ctx)
        if f not in ("ssm", "hybrid"):
            raise ValueError(f"family {f!r} has no chunked prefill "
                             f"(see supports_chunked_prefill)")
        n = jnp.asarray(n_valid, jnp.int32)

        def body(carry, xs):
            cache = carry
            tok, i = xs
            logits, new = self.decode_step(params, cache, tok[:, None],
                                           ctx=ctx)
            keep = i < n
            cache = jax.tree.map(
                lambda a, b: jnp.where(keep, a, b), new, cache)
            return cache, logits[:, 0]

        steps = (jnp.moveaxis(tokens, 1, 0),          # (C, B)
                 jnp.arange(tokens.shape[1]))
        cache, ys = jax.lax.scan(body, cache, steps)
        return jnp.moveaxis(ys, 0, 1), cache          # (B, C, V)

    def decode_step(self, params, cache, tokens, *, ctx: ShardCtx = NO_SHARD,
                    decode_block: Optional[int] = None,
                    page_tables=None, page_block: Optional[int] = None,
                    paged_decode_block: Optional[int] = None, rows=None):
        """One decode step.  ``decode_block`` is the bucket-tuned
        decode-attention cache block resolved by the serving router; it
        selects the *executed* attention sweep (Pallas kernel or blocked
        reference — see ``attention.attention_decode``).  ``None`` keeps
        the plain einsum path; attention-free families ignore it.
        ``page_tables`` (B, nb) + ``page_block`` switch the KV caches to
        the physical block-table layout (serving's paged pool);
        ``paged_decode_block`` (the router's tuned fused ``block_s``)
        makes the sweep consume the tables directly instead of gathering
        a logical view first.  moe: ``rows`` (B,) bool marks the rows in
        flight, and the step also returns the expert layer's load
        (L, held) over them (``transformer.decode_step``)."""
        cfg, f = self.cfg, self.cfg.family
        kw = dict(ctx=ctx, decode_block=decode_block,
                  page_tables=page_tables, page_block=page_block,
                  paged_decode_block=paged_decode_block)
        if f in ("dense", "moe", "vlm"):
            if f == "moe":
                kw["rows"] = rows
            return tf_mod.decode_step(params, cache, tokens, cfg, **kw)
        if f == "ssm":
            return ssm_mod.ssm_decode(params, cache, tokens, cfg, **kw)
        if f == "hybrid":
            return hybrid_mod.hybrid_decode(params, cache, tokens, cfg, **kw)
        if f == "encdec":
            return encdec_mod.encdec_decode(params, cache, tokens, cfg, **kw)
        raise ValueError(f)

    # ------------------------------------------------------------------ #
    # Dry-run stand-ins (assignment: ShapeDtypeStruct, no allocation)
    # ------------------------------------------------------------------ #
    def input_specs(self, shape: ShapeConfig) -> dict:
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        if shape.kind in ("train", "prefill"):
            text = s
            d: dict[str, Any] = {}
            if cfg.family == "vlm":
                text = s - cfg.prefix_tokens
                d["patches"] = jax.ShapeDtypeStruct(
                    (b, cfg.prefix_tokens, cfg.d_model), self.dtype)
            if cfg.family == "encdec":
                d["frames"] = jax.ShapeDtypeStruct(
                    (b, cfg.encoder_tokens, cfg.d_model), self.dtype)
            d["tokens"] = jax.ShapeDtypeStruct((b, text), i32)
            if shape.kind == "train":
                d["labels"] = jax.ShapeDtypeStruct((b, text), i32)
                d["mask"] = jax.ShapeDtypeStruct((b, text), jnp.float32)
            return d
        # decode: one new token against a cache of length s
        return {"tokens": jax.ShapeDtypeStruct((b, 1), i32)}

    def tokens_per_step(self, shape: ShapeConfig) -> int:
        if shape.kind == "decode":
            return shape.global_batch
        return shape.global_batch * shape.seq_len


def build_model(cfg: ModelConfig, **kw) -> Model:
    return Model(cfg, **kw)
