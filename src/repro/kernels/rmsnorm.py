"""Fused RMSNorm — rows blocked by the runtime planner, feature dim resident.

One program normalizes ``block_rows`` tokens: mean-of-squares, rsqrt, scale
by gamma, all in one VMEM pass (vs. 3 HBM passes unfused).  block_rows is
Eq. 1 over token rows: rows per program = tokens / hp, tile-rounded and
VMEM-clamped.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hw import TpuParams, ceil_div, round_up
from repro.core.mapper import MappingPolicy, resolve_lws


def _rmsnorm_kernel(x_ref, g_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    o_ref[...] = (x * rms * g_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def plan_rows(tokens: int, d: int, hw: TpuParams,
              policy: MappingPolicy, dtype_bytes: int) -> int:
    """Row-block size: the lws analogue over token rows."""
    if policy is MappingPolicy.NAIVE:
        return 8
    if policy is MappingPolicy.FIXED:
        return 128
    rows = resolve_lws(tokens, hw.cores_per_chip)
    rows = round_up(min(rows, tokens), 8)
    cap = max(8, (hw.vmem_budget_bytes // (3 * d * dtype_bytes)) // 8 * 8)
    return max(8, min(rows, cap, 4096))


def rmsnorm_pallas(
    x: jax.Array,
    gamma: jax.Array,
    *,
    hw: TpuParams,
    eps: float = 1e-6,
    policy: MappingPolicy = MappingPolicy.AUTO,
    block_rows: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """x: (tokens, d); gamma: (d,)."""
    tokens, d = x.shape
    if block_rows is None:
        block_rows = plan_rows(tokens, d, hw, policy, x.dtype.itemsize)
    tp = round_up(tokens, block_rows)
    xp = jnp.pad(x, ((0, tp - tokens), (0, 0))) if tp != tokens else x
    g2 = gamma.reshape(1, d)
    import functools
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        out_shape=jax.ShapeDtypeStruct((tp, d), x.dtype),
        grid=(tp // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
                  pl.BlockSpec((1, d), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=hw.vmem_budget_bytes),
        interpret=interpret,
    )(xp, g2)
    return out[:tokens] if tp != tokens else out
