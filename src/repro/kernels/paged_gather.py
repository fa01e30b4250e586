"""Paged KV gather — block-table indirection for the serving cache.

The serving pool's physical KV store is a block grid: each pool row of
length T holds T/bs fixed-size blocks, and a request's logical cache is
scattered over whichever physical blocks its ``KVCachePool`` lease
acquired (``serve.kvcache``).  This kernel materializes one request's
*logical* view by gathering its blocks in table order — the read half of
physical paging, paired with the scatter writes in
``models.attention._cache_write``.

Physical block id mapping (column-major over the pool grid, so pool
growth appends new ids without remapping live blocks):

    pid  ->  (row = pid % slots, offset = (pid // slots) * block_size)

On TPU the gather is a Pallas kernel built on
``PrefetchScalarGridSpec``: the block table is a scalar-prefetch operand,
so each grid step's ``BlockSpec`` index_map reads ``table[i]`` and the
DMA engine streams the physical block straight to its logical position —
no materialized index array, one block copy per grid step.  Elsewhere a
``jnp.take`` over precomputed flat indices is the reference (and the
numerics oracle: the two paths are bit-identical, it is a pure copy).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flat_position", "paged_flat_indices", "paged_gather",
           "paged_gather_pallas", "paged_gather_ref",
           "paged_dequant_gather", "paged_dequant_gather_pallas",
           "paged_dequant_gather_ref"]


def flat_position(pid, pos, slots: int, kv_len: int, block_size: int):
    """THE layout invariant, defined once: the flat (slots*kv_len)
    cache position of logical token ``pos`` inside physical block
    ``pid``.  Pure arithmetic over numpy or jax arrays — the scatter
    writes (``models.attention._cache_write``), the prefill page map
    (``serve.engine``), and the gather below all index through this one
    function, so the grid mapping can never desynchronize between
    writers and readers."""
    return ((pid % slots) * kv_len + (pid // slots) * block_size
            + pos % block_size)


def paged_flat_indices(tables: jax.Array, slots: int, kv_len: int,
                       block_size: int) -> jax.Array:
    """Flat (slots*kv_len) positions of each row's logical tokens.

    ``tables`` (slots, nb) holds physical block ids (-1 = unmapped; the
    result clamps those to position 0 — callers mask by cache length, so
    an unmapped block is never *read* meaningfully).  Returns (slots,
    kv_len) int32 indices into the pool flattened as (slots*kv_len, ...).
    """
    t = jnp.arange(kv_len, dtype=jnp.int32)
    bi = t // block_size                                  # logical block
    pid = tables[:, bi]                                   # (slots, kv_len)
    pid = jnp.maximum(pid, 0)                             # clamp unmapped
    return flat_position(pid, t, slots, kv_len, block_size)


def paged_gather_ref(cache: jax.Array, tables: jax.Array,
                     block_size: int) -> jax.Array:
    """Reference gather: cache (B, T, ...) physical -> (B, T, ...) logical.

    Example::

        kr = paged_gather_ref(k_cache, tables, block_size=16)
    """
    b, t = cache.shape[:2]
    idx = paged_flat_indices(tables[:, : -(-t // block_size)], b, t,
                             block_size)
    flat = cache.reshape((b * t,) + cache.shape[2:])
    return jnp.take(flat, idx.reshape(-1), axis=0).reshape(cache.shape)


def _gather_kernel(table_ref, c_ref, o_ref):
    # pure block copy: the index_map already routed the right physical
    # block into c_ref for this grid step
    del table_ref
    o_ref[...] = c_ref[...]


def paged_gather_pallas(cache: jax.Array, tables: jax.Array,
                        block_size: int, *,
                        interpret: bool = False) -> jax.Array:
    """Pallas block-table gather: cache (B, T, G, D) -> logical view.

    Grid = (B, T/bs); the scalar-prefetched table drives the input
    BlockSpec's index_map, so grid step (b, i) DMAs physical block
    ``tables[b, i]`` into logical block i of row b.
    """
    b, t = cache.shape[:2]
    bs = block_size
    nb = t // bs
    assert t % bs == 0, (t, bs)
    # physical block pid -> flat block index (row-major over (B, nb)):
    # row = pid % B, block-offset = pid // B
    pid = jnp.maximum(tables[:, :nb], 0).astype(jnp.int32)
    flat_block = (pid % b) * nb + (pid // b)              # (B, nb)
    blocks = cache.reshape((b * nb, bs) + cache.shape[2:])
    tail = cache.shape[2:]
    ones = (0,) * len(tail)

    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, nb),
            in_specs=[pl.BlockSpec(
                (1, bs) + tail,
                lambda bi, i, tbl: (tbl[bi, i], 0) + ones)],
            out_specs=pl.BlockSpec(
                (1, bs) + tail,
                lambda bi, i, tbl: (bi * nb + i, 0) + ones),
        ),
        out_shape=jax.ShapeDtypeStruct(blocks.shape, cache.dtype),
        interpret=interpret,
    )(flat_block, blocks)
    return out.reshape(cache.shape)


def paged_gather(cache: jax.Array, tables: jax.Array, block_size: int, *,
                 use_pallas: bool = False,
                 interpret: bool = False) -> jax.Array:
    """Dispatch the gather: Pallas kernel when requested and legal (T a
    multiple of ``block_size``), ``jnp.take`` reference otherwise."""
    if use_pallas and cache.shape[1] % block_size == 0:
        return paged_gather_pallas(cache, tables, block_size,
                                   interpret=interpret)
    return paged_gather_ref(cache, tables, block_size)


# --------------------------------------------------------------------------- #
# int8 variant: dequant fused into the gather (scales ride the table)
# --------------------------------------------------------------------------- #


def paged_dequant_gather_ref(cache: jax.Array, scale: jax.Array,
                             tables: jax.Array, block_size: int,
                             out_dtype=jnp.float32) -> jax.Array:
    """Reference fused dequant-gather for the int8 pool.

    ``cache`` (B, T, G, D) int8 codes on the physical grid; ``scale``
    (B, T/bs, G) f32 per-(physical block, kv head) symmetric scales,
    indexed by physical coordinates ``[pid % B, pid // B]``.  Returns
    the request-logical dequantized view ``codes * scale`` in
    ``out_dtype`` — the same one-take schedule as ``paged_gather_ref``,
    with the scale gathered by the *same* flat block index
    (``flat_token // bs == (pid % B) * nb + pid // B``: the layout
    invariant keeps codes and scales pointing at one physical block).
    """
    b, t = cache.shape[:2]
    nb = -(-t // block_size)
    pid = jnp.maximum(tables[:, :nb], 0).astype(jnp.int32)
    flat_block = (pid % b) * nb + (pid // b)              # (B, nb)
    codes = paged_gather_ref(cache, tables, block_size)
    sc = jnp.take(scale.reshape(b * nb, -1), flat_block.reshape(-1),
                  axis=0).reshape(b, nb, scale.shape[-1])
    sc = jnp.repeat(sc, block_size, axis=1)[:, :t]        # (B, T, G)
    return codes.astype(out_dtype) * sc[..., None].astype(out_dtype)


def _dequant_gather_kernel(table_ref, c_ref, s_ref, o_ref):
    # the index_map routed this grid step's physical block AND its scale
    # row here; dequant happens in-register, the int8 codes never
    # materialize at f32 width outside this block
    del table_ref
    o_ref[...] = (c_ref[...].astype(o_ref.dtype)
                  * s_ref[...][..., None].astype(o_ref.dtype))


def paged_dequant_gather_pallas(cache: jax.Array, scale: jax.Array,
                                tables: jax.Array, block_size: int, *,
                                out_dtype=jnp.float32,
                                interpret: bool = False) -> jax.Array:
    """Pallas fused dequant-gather: grid step (b, i) DMAs physical int8
    block ``tables[b, i]`` and its (1, 1, G) scale row — both BlockSpecs
    read the same scalar-prefetched flat block index — and writes the
    dequantized logical block.  The scales are viewed as (B*nb, 1, G) so
    the scale block's last two dims are whole, as the TPU tiling
    requires."""
    b, t = cache.shape[:2]
    bs = block_size
    nb = t // bs
    assert t % bs == 0, (t, bs)
    g = cache.shape[2]
    pid = jnp.maximum(tables[:, :nb], 0).astype(jnp.int32)
    flat_block = (pid % b) * nb + (pid // b)              # (B, nb)
    blocks = cache.reshape((b * nb, bs) + cache.shape[2:])
    scale_flat = scale.reshape(b * nb, 1, g)
    tail = cache.shape[2:]
    ones = (0,) * len(tail)

    out = pl.pallas_call(
        _dequant_gather_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, nb),
            in_specs=[
                pl.BlockSpec((1, bs) + tail,
                             lambda bi, i, tbl: (tbl[bi, i], 0) + ones),
                pl.BlockSpec((1, 1, g),
                             lambda bi, i, tbl: (tbl[bi, i], 0, 0)),
            ],
            out_specs=pl.BlockSpec(
                (1, bs) + tail,
                lambda bi, i, tbl: (bi * nb + i, 0) + ones),
        ),
        out_shape=jax.ShapeDtypeStruct(blocks.shape, out_dtype),
        interpret=interpret,
    )(flat_block, blocks, scale_flat)
    return out.reshape(cache.shape[:2] + tail)


def paged_dequant_gather(cache: jax.Array, scale: jax.Array,
                         tables: jax.Array, block_size: int, *,
                         out_dtype=jnp.float32, use_pallas: bool = False,
                         interpret: bool = False) -> jax.Array:
    """Dispatch the fused dequant-gather (int8 pool read half)."""
    if use_pallas and cache.shape[1] % block_size == 0:
        return paged_dequant_gather_pallas(cache, scale, tables,
                                           block_size, out_dtype=out_dtype,
                                           interpret=interpret)
    return paged_dequant_gather_ref(cache, scale, tables, block_size,
                                    out_dtype=out_dtype)
