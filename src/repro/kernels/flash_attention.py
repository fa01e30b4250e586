"""Causal flash attention — the framework's perf-critical prefill kernel.

Canonical TPU schedule: grid (q_blocks, kv_blocks) with the KV dimension
innermost/sequential; running (max, sum, acc) live in VMEM scratch across
the KV sweep of each Q block and flush once.  (block_q, block_k) are
resolved by ``core.mapper.plan_attention_blocks`` — the Eq. 1 analogue over
query rows with the VMEM clamp.

Adaptation note (DESIGN.md §2): the GPU flash algorithm tiles over SMs with
shared-memory staging; on TPU the same dataflow maps onto the grid +
BlockSpec machinery with VMEM-resident running statistics, and the MXU
wants ≥128-wide tiles, which the planner enforces.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hw import TpuParams, round_up
from repro.core.mapper import AttentionPlan, MappingPolicy, plan_attention_blocks

_NEG_INF = float("-inf")


def _flash_kernel(off_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                  *, scale: float, causal: bool):
    qi = pl.program_id(0)
    ki = pl.program_id(1)
    bq = q_ref.shape[0]
    bk = k_ref.shape[0]

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32) * scale
    k = k_ref[...].astype(jnp.float32)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (bq, bk)

    if causal:
        q_pos = (qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
                 + off_ref[0])
        k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(k_pos <= q_pos, s, _NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - m_safe[:, None])
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)

    m_ref[...] = m_new
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
        p, v_ref[...].astype(jnp.float32), preferred_element_type=jnp.float32)

    @pl.when(ki == pl.num_programs(1) - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    hw: TpuParams,
    causal: bool = True,
    scale: float | None = None,
    policy: MappingPolicy = MappingPolicy.AUTO,
    plan: AttentionPlan | None = None,
    q_offset=None,
    interpret: bool = False,
) -> jax.Array:
    """Single-head attention: q (sq, d), k/v (skv, d).  Heads/batch vmap.

    ``q_offset`` is the absolute position of query row 0 (an int or a
    traced scalar; ``None`` aligns the queries to the END of the keys,
    ``skv - sq``, the cached-prefix case).  It is a scalar-prefetch
    operand, so a chunked prefill's traced start offset shares one
    compiled kernel across chunks."""
    sq, d = q.shape
    skv = k.shape[0]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    if plan is None:
        plan = plan_attention_blocks(sq, skv, d, hw, policy,
                                     dtype_bytes=q.dtype.itemsize)
    bq, bk = min(plan.block_q, round_up(sq, 8)), min(plan.block_k, round_up(skv, 128))
    sqp, skvp = round_up(sq, bq), round_up(skv, bk)
    if q_offset is None:
        q_offset = skv - sq  # causal alignment for cached prefixes
    off = jnp.asarray(q_offset, jnp.int32).reshape(1)
    qp = jnp.pad(q, ((0, sqp - sq), (0, 0))) if sqp != sq else q
    kp = jnp.pad(k, ((0, skvp - skv), (0, 0))) if skvp != skv else k
    vp = jnp.pad(v, ((0, skvp - skv), (0, 0))) if skvp != skv else v
    if skvp != skv and not causal:
        raise ValueError("non-causal attention requires skv % block_k == 0")

    kern = functools.partial(_flash_kernel, scale=scale,
                             causal=causal or skvp != skv)
    out = pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((sqp, d), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(sqp // bq, skvp // bk),
            in_specs=[
                pl.BlockSpec((bq, d), lambda i, j, o: (i, 0)),
                pl.BlockSpec((bk, d), lambda i, j, o: (j, 0)),
                pl.BlockSpec((bk, d), lambda i, j, o: (j, 0)),
            ],
            out_specs=pl.BlockSpec((bq, d), lambda i, j, o: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((bq,), jnp.float32),
                pltpu.VMEM((bq,), jnp.float32),
                pltpu.VMEM((bq, d), jnp.float32),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=hw.vmem_budget_bytes,
        ),
        interpret=interpret,
        name="flash_attention",
    )(off, qp, kp, vp)
    return out[:sq] if sqp != sq else out
