"""Step builders: train / prefill / decode, with runtime-resolved mapping.

``make_train_step`` composes the whole production recipe:
  * microbatch count from ``core.mapper.plan_microbatch`` (Eq. 1 at the
    mesh tier, HBM-budget constrained; under ``MappingPolicy.TUNED`` it
    resolves through the ``repro.tuner`` dispatch layer's fallback path),
  * per-layer remat (scan-over-layers bodies),
  * grad accumulation in f32 with ONE reduction at the end
    (``reduce_once``) rather than per microbatch,
  * optional int8 round-trip on grads (cross-pod compression numerics),
  * AdamW with ZeRO-1 sharded states.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.core.mapper import MappingPolicy, MeshPlan, plan_microbatch
from repro.models.model import Model
from repro.optim import AdamWConfig, adamw_update, compress_grads_int8, init_opt_state
from repro.runtime.sharding import Plan, make_ctx

PyTree = Any


# --------------------------------------------------------------------------- #
# Activation-memory model (for the microbatch Eq. 1)
# --------------------------------------------------------------------------- #


def activation_bytes_per_seq(cfg: ModelConfig, seq: int, tp: int,
                             sequence_parallel: bool = True) -> float:
    """Bytes of per-microbatch live memory one sequence contributes/device:
    remat-saved residuals (seq x d_model per layer, sequence-sharded under
    SP, x1.5 working-set slack) + f32 logits (vocab-sharded) + MoE dispatch
    buffers."""
    sp = tp if sequence_parallel else 1
    dtype_bytes = 2 if cfg.dtype == "bfloat16" else 4
    layers = cfg.num_layers + cfg.encoder_layers
    stash = 1.5 * layers * (seq / sp) * cfg.d_model * dtype_bytes
    vshard = tp if cfg.vocab_size % tp == 0 else 1
    logits = 2.0 * seq * cfg.vocab_size * 4 / vshard
    moe = 0.0
    if cfg.moe_experts:
        moe = 3.0 * seq * cfg.moe_topk * 1.25 * cfg.d_model * dtype_bytes / tp
    return stash + logits + moe


def activation_budget(cfg: ModelConfig, plan: Plan,
                      hbm: float = 15.2 * 1024**3,
                      misc: float = 1.0 * 1024**3) -> float:
    """HBM left for remat stash after params/grads/moments — the memory
    side of the runtime mapping decision (Eq. 1's memory regime)."""
    tp, dp = plan.info.tp, plan.info.dp
    db = 2 if cfg.dtype == "bfloat16" else 4
    acc = 2 if plan.accum_dtype == "bfloat16" else 4
    mom = 2 if plan.moment_dtype == "bfloat16" else 4
    n = cfg.n_params()
    shard = tp * (dp if plan.fsdp else 1)
    state = n * db / shard + 2 * n * acc / shard + 2 * n * mom / (tp * dp)
    return max(0.5 * 1024**3, hbm - state - misc)


def resolve_microbatches(cfg: ModelConfig, shape: ShapeConfig, plan: Plan,
                         policy: MappingPolicy = MappingPolicy.AUTO
                         ) -> MeshPlan:
    """Mesh-tier Eq. 1, routed through the tuner dispatch layer.

    The mesh tier has no refine cost model (the objective is HBM fit, not
    a differentiable roofline), so ``TUNED`` falls back cleanly to the
    Eq. 1 plan — memoized in the tuning cache with zero probes.  The
    other policies resolve through ``plan_microbatch`` directly."""
    gb, dp = shape.global_batch, plan.info.dp
    abs_ = activation_bytes_per_seq(cfg, shape.seq_len, plan.info.tp)
    budget = activation_budget(cfg, plan)
    if MappingPolicy(policy) is MappingPolicy.TUNED:
        from repro.tuner import resolve_mesh_plan
        return resolve_mesh_plan(gb, dp, abs_, budget, policy=policy)
    return plan_microbatch(gb, dp, abs_, budget, policy=policy)


# --------------------------------------------------------------------------- #
# Train step
# --------------------------------------------------------------------------- #


@dataclasses.dataclass(frozen=True)
class StepConfig:
    remat: str = "full"                   # none | dots | full | moe
    microbatches: int = 1
    compress_grads: bool = False          # int8 round-trip (cross-pod sim)
    aux_weight: float = 0.01
    # §Perf levers (beyond-paper): fp8 EP all-to-all, capacity slack,
    # static banded local attention for local:global archs
    moe_fp8_a2a: bool = False
    moe_slack: float = 1.25
    banded_local: bool = False


def make_train_step(model: Model, opt_cfg: AdamWConfig, plan: Plan,
                    step_cfg: StepConfig):
    """Returns train_step(state, batch) -> (state, metrics).

    state = {"params": ..., "opt": {m, v, step}}.
    batch leaves have leading dim = global batch.
    """
    from repro.runtime.sharding import param_shardings
    ctx = make_ctx(plan)
    ctx.flags.update({"moe_fp8_a2a": step_cfg.moe_fp8_a2a,
                      "moe_slack": step_cfg.moe_slack,
                      "banded_local": step_cfg.banded_local})
    k = step_cfg.microbatches
    acc_dtype = jnp.dtype(plan.accum_dtype)
    grad_sh = param_shardings(model.specs, plan) \
        if plan.info.mesh is not None else None

    def constrain_grads(g):
        """Keep the accumulator in the param sharding (grads of FSDP
        params must reduce-scatter back, not replicate)."""
        if grad_sh is None:
            return g
        return jax.lax.with_sharding_constraint(g, grad_sh)

    def loss_fn(params, mb):
        loss, metrics = model.loss(params, mb, remat=step_cfg.remat, ctx=ctx)
        return loss, metrics

    def train_step(state, batch):
        params = state["params"]
        if k == 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            grads = constrain_grads(
                jax.tree.map(lambda g: g.astype(acc_dtype), grads))
        else:
            # split batch into k microbatches along the leading dim;
            # accumulate grads locally, reduce ONCE via the final psum
            # GSPMD inserts for the grads (reduce_once schedule).
            mbs = jax.tree.map(
                lambda x: x.reshape((k, x.shape[0] // k) + x.shape[1:]),
                batch)

            def acc_body(carry, mb):
                mb = jax.lax.optimization_barrier(mb)
                g_acc, loss_acc = carry
                (loss, _), g = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, mb)
                g_acc = jax.tree.map(
                    lambda a, b: a + b.astype(acc_dtype), g_acc, g)
                return (constrain_grads(g_acc), loss_acc + loss), None

            g0 = constrain_grads(jax.tree.map(
                lambda p: jnp.zeros(p.shape, acc_dtype), params))
            (grads, loss), _ = jax.lax.scan(acc_body, (g0, 0.0), mbs)
            grads = jax.tree.map(lambda g: g / k, grads)
            loss = loss / k
            metrics = {}
        if step_cfg.compress_grads:
            grads = compress_grads_int8(
                grads, jax.random.fold_in(jax.random.key(0),
                                          state["opt"]["step"]))
        params, opt, om = adamw_update(params, grads, state["opt"], opt_cfg)
        metrics = dict(metrics, loss=loss, **om)
        return {"params": params, "opt": opt}, metrics

    return train_step


def init_train_state(model: Model, rng, plan: Optional[Plan] = None) -> dict:
    params = model.init(rng)
    mdt = jnp.dtype(plan.moment_dtype) if plan else jnp.float32
    return {"params": params, "opt": init_opt_state(params, mdt)}


def abstract_train_state(model: Model, plan: Optional[Plan] = None) -> dict:
    params = model.abstract_params()
    mdt = jnp.dtype(plan.moment_dtype) if plan else jnp.float32
    mk = lambda p: jax.ShapeDtypeStruct(p.shape, mdt)
    return {"params": params,
            "opt": {"m": jax.tree.map(mk, params),
                    "v": jax.tree.map(mk, params),
                    "step": jax.ShapeDtypeStruct((), jnp.int32)}}


# --------------------------------------------------------------------------- #
# Serve steps
# --------------------------------------------------------------------------- #
#
# Each serve step runs under a ``jax.named_scope`` (``serve.decode_step``,
# ``serve.chunk_prefill_step``, ``serve.prefill_step``), so its operations
# carry that name in their HLO ``op_name`` metadata.


def make_prefill_step(model: Model, plan: Plan, max_len: Optional[int],
                      flags: Optional[dict] = None):
    """``max_len=None`` pads the cache only to the prompt's own (bucketed)
    length — the serving engine pads rows to the pool length on insert, so
    one jitted prefill serves every prompt bucket.  ``last_pos`` (B,)
    selects each row's true final-token logits for right-padded prompts
    (defaults to the fixed-batch position -1 behaviour).

    ``prefill_tiles`` — the router-resolved flash (block_q, block_k) —
    is meant to be jitted as a STATIC argument: a new tile pair is a new
    prompt bucket, and bucket changes are the (lattice-bounded) compile
    events.  ``None`` keeps the GSPMD prefill path byte-identical.

    ``pad_to`` (static, ``max_len=None`` only) overrides the cache pad
    target when the row is LONGER than the token batch — the vlm
    family's rows carry ``prefix_tokens`` patch positions before token
    0, so its serving cache pads to ``prefix + bucket``, not the token
    bucket alone.  ``None`` (the default) keeps the original behaviour
    byte-identical."""
    ctx = make_ctx(plan)
    ctx.flags.update(flags or {})

    def prefill_step(params, batch, last_pos=None, prefill_tiles=None,
                     pad_to=None):
        ml = max_len if max_len is not None else (
            pad_to if pad_to is not None else batch["tokens"].shape[1])
        with jax.named_scope("serve.prefill_step"):
            return model.prefill(params, batch, ml, last_pos=last_pos,
                                 prefill_tiles=prefill_tiles, ctx=ctx)

    return prefill_step


def make_chunk_prefill_step(model: Model, plan: Plan,
                            flags: Optional[dict] = None):
    """Chunked-prefill step for the serving engine (see
    ``Model.prefill_chunk``).  ``prefill_tiles`` is meant to be jitted
    STATIC like the whole-prompt path; the chunk width C and row-cache
    length are static by shape, while the start offset (``cache["pos"]``)
    and ``n_valid`` stay traced — so the compile set is bounded by the
    (C, cache_len, tiles) lattice, not by prompt lengths."""
    ctx = make_ctx(plan)
    ctx.flags.update(flags or {})

    def chunk_prefill_step(params, cache, tokens, n_valid,
                           prefill_tiles=None):
        with jax.named_scope("serve.chunk_prefill_step"):
            return model.prefill_chunk(params, cache, tokens, n_valid,
                                       prefill_tiles=prefill_tiles, ctx=ctx)

    return chunk_prefill_step


def make_decode_step(model: Model, plan: Plan,
                     flags: Optional[dict] = None):
    """``decode_block`` is the bucket-tuned decode-attention mapping the
    serving engine threads from ``BucketRouter`` into the executed step;
    jit it as a static argument (a new block is a new bucket, and bucket
    changes are the compile events the lattice bounds).  ``None`` keeps
    the plain einsum decode path.  ``page_tables`` (a traced (B, nb)
    array — live tables change every admission) + ``page_block`` (static)
    switch the KV caches to the physical block-table layout;
    ``paged_decode_block`` (static, router-tuned) fuses the table read
    into the attention sweep itself."""
    ctx = make_ctx(plan)
    ctx.flags.update(flags or {})

    def decode_step(params, cache, tokens, decode_block=None,
                    page_tables=None, page_block=None,
                    paged_decode_block=None):
        with jax.named_scope("serve.decode_step"):
            return model.decode_step(params, cache, tokens, ctx=ctx,
                                     decode_block=decode_block,
                                     page_tables=page_tables,
                                     page_block=page_block,
                                     paged_decode_block=paged_decode_block)

    return decode_step
