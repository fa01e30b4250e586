"""Near-neighbour search — blocked L2 NN with running argmin in scratch.

Grid: (query_blocks, ref_blocks) with the ref dimension innermost
(sequential); the per-query running (min distance, min index) live in VMEM
scratch across the ref sweep.  Distances go through the MXU as
``|q|^2 - 2 q·r + |r|^2``.  Query block size is the ``lws`` analogue.

This is one of the kernels the paper flags as "atypical" under its
mapping (§3): the reduction over refs makes lws interact with cache reuse
— on TPU the ref pool streams through VMEM once per query block, so larger
query blocks amortize that traffic (beyond-paper note in EXPERIMENTS.md).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hw import TpuParams, round_up
from repro.core.mapper import MappingPolicy, resolve_lws

_BIG = 3.4e38  # plain float: jnp constants would be captured as tracers


def plan_query_block(nq: int, d: int, hw: TpuParams,
                     policy: MappingPolicy, dtype_bytes: int) -> int:
    if policy is MappingPolicy.NAIVE:
        return 8
    if policy is MappingPolicy.FIXED:
        return 128
    bq = round_up(resolve_lws(nq, hw.cores_per_chip), 8)
    cap = max(8, (hw.vmem_budget_bytes // (8 * max(d, 128) * dtype_bytes)) // 8 * 8)
    return min(bq, cap, 2048)


def _nn_kernel(q_ref, r_ref, idx_ref, dist_ref, mind_ref, mini_ref):
    ri = pl.program_id(1)
    br = r_ref.shape[0]

    @pl.when(ri == 0)
    def _init():
        mind_ref[...] = jnp.full_like(mind_ref, _BIG)
        mini_ref[...] = jnp.zeros_like(mini_ref)

    q = q_ref[...].astype(jnp.float32)          # (bq, d)
    r = r_ref[...].astype(jnp.float32)          # (br, d)
    d2 = (
        jnp.sum(q * q, -1, keepdims=True)
        - 2.0 * jnp.dot(q, r.T, preferred_element_type=jnp.float32)
        + jnp.sum(r * r, -1)[None, :]
    )                                            # (bq, br)
    blk_min = jnp.min(d2, axis=-1)
    blk_arg = jnp.argmin(d2, axis=-1).astype(jnp.int32) + ri * br
    better = blk_min < mind_ref[...]
    mind_ref[...] = jnp.where(better, blk_min, mind_ref[...])
    mini_ref[...] = jnp.where(better, blk_arg, mini_ref[...])

    @pl.when(ri == pl.num_programs(1) - 1)
    def _flush():
        idx_ref[...] = mini_ref[...]
        dist_ref[...] = mind_ref[...]


def nn_search_pallas(
    queries: jax.Array,
    refs: jax.Array,
    *,
    hw: TpuParams,
    policy: MappingPolicy = MappingPolicy.AUTO,
    block_q: int | None = None,
    block_r: int = 512,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """queries (Q, D), refs (R, D) -> (idx int32 (Q,), sq-dist f32 (Q,))."""
    nq, d = queries.shape
    nr = refs.shape[0]
    if block_q is None:
        block_q = plan_query_block(nq, d, hw, policy, queries.dtype.itemsize)
    block_q = min(block_q, round_up(nq, 8))
    block_r = min(block_r, round_up(nr, 8))
    nqp, nrp = round_up(nq, block_q), round_up(nr, block_r)
    qp = jnp.pad(queries, ((0, nqp - nq), (0, 0))) if nqp != nq else queries
    # pad refs with +BIG rows so they never win the argmin
    rp = jnp.pad(refs, ((0, nrp - nr), (0, 0)), constant_values=1e18) \
        if nrp != nr else refs
    idx, dist = pl.pallas_call(
        _nn_kernel,
        out_shape=(jax.ShapeDtypeStruct((nqp,), jnp.int32),
                   jax.ShapeDtypeStruct((nqp,), jnp.float32)),
        grid=(nqp // block_q, nrp // block_r),
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_r, d), lambda i, j: (j, 0)),
        ],
        out_specs=(pl.BlockSpec((block_q,), lambda i, j: (i,)),
                   pl.BlockSpec((block_q,), lambda i, j: (i,))),
        scratch_shapes=[pltpu.VMEM((block_q,), jnp.float32),
                        pltpu.VMEM((block_q,), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=hw.vmem_budget_bytes,
        ),
        interpret=interpret,
    )(qp, rp)
    return idx[:nq], dist[:nq]
