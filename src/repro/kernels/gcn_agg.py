"""GCN neighbourhood aggregation — block-sparse SpMM on the MXU.

Hardware adaptation (DESIGN.md §2): on Vortex/GPU this is an irregular
gather-sum over edge lists; TPUs have no efficient arbitrary gather, so the
paper's aggregation is re-blocked as ``A_hat @ X`` with the normalized
adjacency in dense (bm x bk) tiles and a precomputed per-tile occupancy
mask.  Empty tiles skip the MXU work (``pl.when``) — the block-sparsity
analogue of skipping absent neighbours.  Graph locality (typical for GCN
datasets after clustering) makes most off-diagonal tiles empty.

Grid: (node_blocks, src_blocks) with src innermost; f32 accumulation in
scratch, one flush per node block.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hw import TpuParams, round_up
from repro.core.mapper import MappingPolicy, resolve_lws


def plan_node_block(n: int, f: int, hw: TpuParams, policy: MappingPolicy,
                    dtype_bytes: int) -> int:
    if policy is MappingPolicy.NAIVE:
        return 8
    if policy is MappingPolicy.FIXED:
        return 128
    bn = round_up(resolve_lws(n, hw.cores_per_chip), 8)
    cap = max(8, (hw.vmem_budget_bytes // (4 * max(f, 128) * dtype_bytes)) // 8 * 8)
    return min(bn, cap, 1024)


def _gcn_kernel(mask_ref, a_ref, x_ref, o_ref, acc_ref):
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(mask_ref[0, 0] != 0)
    def _work():
        acc_ref[...] += jnp.dot(
            a_ref[...].astype(jnp.float32),
            x_ref[...].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )

    @pl.when(si == pl.num_programs(1) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def tile_occupancy(adj: jax.Array, bm: int, bk: int) -> jax.Array:
    """(nb, kb) int32 mask: 1 where the adjacency tile has any edge."""
    n, m = adj.shape
    np_, mp_ = round_up(n, bm), round_up(m, bk)
    a = jnp.pad(adj, ((0, np_ - n), (0, mp_ - m)))
    t = a.reshape(np_ // bm, bm, mp_ // bk, bk)
    return (jnp.abs(t).sum(axis=(1, 3)) > 0).astype(jnp.int32)


def gcn_aggregate_pallas(
    adj_norm: jax.Array,
    feats: jax.Array,
    *,
    hw: TpuParams,
    policy: MappingPolicy = MappingPolicy.AUTO,
    block_n: int | None = None,
    block_s: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """adj_norm (N, N) dense normalized adjacency; feats (N, F)."""
    n, n2 = adj_norm.shape
    assert n == n2
    f = feats.shape[1]
    if block_n is None:
        block_n = plan_node_block(n, f, hw, policy, feats.dtype.itemsize)
    block_n = min(block_n, round_up(n, 8))
    block_s = min(block_s, round_up(n, 8))
    np_, sp_ = round_up(n, block_n), round_up(n, block_s)
    a = jnp.pad(adj_norm, ((0, np_ - n), (0, sp_ - n)))
    x = jnp.pad(feats, ((0, sp_ - n), (0, 0)))
    occ = tile_occupancy(a, block_n, block_s)
    out = pl.pallas_call(
        _gcn_kernel,
        out_shape=jax.ShapeDtypeStruct((np_, f), feats.dtype),
        grid=(np_ // block_n, sp_ // block_s),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (i, j)),
            pl.BlockSpec((block_n, block_s), lambda i, j: (i, j)),
            pl.BlockSpec((block_s, f), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, f), lambda i, j: (i, 0)),
        scratch_shapes=[pltpu.VMEM((block_n, f), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=hw.vmem_budget_bytes,
        ),
        interpret=interpret,
    )(occ, a, x)
    return out[:n] if np_ != n else out
