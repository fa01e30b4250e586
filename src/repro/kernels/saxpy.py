"""saxpy — y = a*x + y with runtime-resolved blocks (scalar via SMEM-style
scalar prefetch is overkill here; the scalar rides as a (1,1) operand)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hw import TpuParams
from repro.core.mapper import BlockPlan, MappingPolicy, plan_vector_blocks
from repro.core.workload import saxpy as saxpy_workload


def _saxpy_kernel(a_ref, x_ref, y_ref, o_ref):
    o_ref[...] = a_ref[0] * x_ref[...] + y_ref[...]


def saxpy_pallas(
    a: jax.Array,
    x: jax.Array,
    y: jax.Array,
    *,
    hw: TpuParams,
    policy: MappingPolicy = MappingPolicy.AUTO,
    plan: BlockPlan | None = None,
    interpret: bool = False,
) -> jax.Array:
    assert x.shape == y.shape and x.ndim == 1
    n = x.shape[0]
    if plan is None:
        plan = plan_vector_blocks(
            saxpy_workload(n, dtype_bytes=x.dtype.itemsize), hw, policy)
    block = plan.block_elems
    pad = plan.padded_gws - n
    xp = jnp.pad(x, (0, pad)) if pad else x
    yp = jnp.pad(y, (0, pad)) if pad else y
    a1 = jnp.reshape(a.astype(x.dtype), (1,))
    out = pl.pallas_call(
        _saxpy_kernel,
        out_shape=jax.ShapeDtypeStruct(xp.shape, x.dtype),
        grid=(plan.grid,),
        in_specs=[pl.BlockSpec((1,), lambda i: (0,)),
                  pl.BlockSpec((block,), lambda i: (i,)),
                  pl.BlockSpec((block,), lambda i: (i,))],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=hw.vmem_budget_bytes),
        interpret=interpret,
    )(a1, xp, yp)
    return out[:n] if pad else out
