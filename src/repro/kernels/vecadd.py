"""vecadd — the paper's Fig. 1 kernel, mapped by the runtime block planner.

The ``lws`` analogue is ``plan.block_elems``: the number of elements one
program instance covers.  The four policies (naive / fixed / auto / tuned)
produce different (block, grid) decompositions of the same gws, mirroring
Fig. 1's traces; ``tuned`` refines the auto seed through ``repro.tuner``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hw import TpuParams
from repro.core.mapper import BlockPlan, MappingPolicy, plan_vector_blocks
from repro.core.workload import vecadd as vecadd_workload


def _vecadd_kernel(x_ref, y_ref, o_ref):
    o_ref[...] = x_ref[...] + y_ref[...]


def vecadd_pallas(
    x: jax.Array,
    y: jax.Array,
    *,
    hw: TpuParams,
    policy: MappingPolicy = MappingPolicy.AUTO,
    plan: BlockPlan | None = None,
    interpret: bool = False,
) -> jax.Array:
    """c = a + b with runtime-resolved BlockSpec (Eq. 1 at tier 1/2)."""
    assert x.shape == y.shape and x.ndim == 1
    n = x.shape[0]
    if plan is None:
        plan = plan_vector_blocks(
            vecadd_workload(n, dtype_bytes=x.dtype.itemsize), hw, policy)
    block = plan.block_elems
    pad = plan.padded_gws - n
    xp = jnp.pad(x, (0, pad)) if pad else x
    yp = jnp.pad(y, (0, pad)) if pad else y
    out = pl.pallas_call(
        _vecadd_kernel,
        out_shape=jax.ShapeDtypeStruct(xp.shape, x.dtype),
        grid=(plan.grid,),
        in_specs=[pl.BlockSpec((block,), lambda i: (i,)),
                  pl.BlockSpec((block,), lambda i: (i,))],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=hw.vmem_budget_bytes),
        interpret=interpret,
    )(xp, yp)
    return out[:n] if pad else out
