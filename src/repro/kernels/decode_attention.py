"""Flash-decode — single-token attention over a long KV cache.

Grid sweeps the cache in ``block_s`` chunks (the ``lws`` analogue over
cache positions) keeping running (max, sum, acc) in scratch — the split-KV
schedule that turns a bandwidth-bound O(S·d) read into a pipelined sweep.
Ragged caches are handled with a scalar ``cache_len`` mask.

At the mesh tier the framework additionally shards the cache's sequence
dimension over the ``data`` axis when batch < data-parallel size (the
long_500k shapes) and combines partial softmaxes with a psum of
(m, l, acc) — see models/attention.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hw import TpuParams, round_up
from repro.core.mapper import MappingPolicy, resolve_lws

_NEG_INF = float("-inf")


def plan_cache_block(s: int, d: int, hw: TpuParams,
                     policy: MappingPolicy, dtype_bytes: int) -> int:
    if policy is MappingPolicy.NAIVE:
        return 128
    if policy is MappingPolicy.FIXED:
        return 512
    bs = round_up(resolve_lws(s, hw.cores_per_chip), 128)
    cap = max(128, (hw.vmem_budget_bytes // (4 * max(d, 128) * dtype_bytes))
              // 128 * 128)
    return min(bs, cap, 8192)


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                   *, scale: float):
    si = pl.program_id(0)
    bs = k_ref.shape[0]

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32) * scale          # (1, d)
    k = k_ref[...].astype(jnp.float32)                  # (bs, d)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (1, bs)
    pos = si * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    s = jnp.where(pos < len_ref[0], s, _NEG_INF)

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

    @pl.when(si == pl.num_programs(0) - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def decode_attention_pallas(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    cache_len: jax.Array | int | None = None,
    *,
    hw: TpuParams,
    scale: float | None = None,
    policy: MappingPolicy = MappingPolicy.AUTO,
    block_s: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """q (d,), caches (S, d) -> (d,).  Batch/heads via vmap."""
    s, d = k_cache.shape
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    if block_s is None:
        block_s = plan_cache_block(s, d, hw, policy, k_cache.dtype.itemsize)
    block_s = min(block_s, round_up(s, 128))
    sp = round_up(s, block_s)
    kp = jnp.pad(k_cache, ((0, sp - s), (0, 0))) if sp != s else k_cache
    vp = jnp.pad(v_cache, ((0, sp - s), (0, 0))) if sp != s else v_cache
    # the length is a scalar-prefetch (SMEM) operand: a (1,) VMEM block
    # over it is refused by the TPU tiling rules once vmap batches it
    clen = jnp.asarray(s if cache_len is None else cache_len,
                       jnp.int32).reshape(1)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale),
        out_shape=jax.ShapeDtypeStruct((1, d), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(sp // block_s,),
            in_specs=[
                pl.BlockSpec((1, d), lambda i, n: (0, 0)),
                pl.BlockSpec((block_s, d), lambda i, n: (i, 0)),
                pl.BlockSpec((block_s, d), lambda i, n: (i, 0)),
            ],
            out_specs=pl.BlockSpec((1, d), lambda i, n: (0, 0)),
            scratch_shapes=[
                pltpu.VMEM((1,), jnp.float32),
                pltpu.VMEM((1,), jnp.float32),
                pltpu.VMEM((1, d), jnp.float32),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=hw.vmem_budget_bytes),
        interpret=interpret,
        name="decode_attention",
    )(clen, q.reshape(1, d), kp, vp)
    return out[0]
