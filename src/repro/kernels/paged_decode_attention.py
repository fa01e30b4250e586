"""Fused table-consuming paged flash decode — zero-materialization reads.

``paged_gather`` + ``decode_attention`` pays one full extra HBM round
trip per decode step: the block-table gather materializes a logical KV
view that the flash sweep immediately re-reads.  On a memory-bound
kernel that doubles the traffic that sets the roofline.  This kernel
fuses the indirection into the sweep itself: the per-row block table is
a ``PrefetchScalarGridSpec`` scalar-prefetch operand (the idiom proven
in ``kernels/paged_gather``), so each grid step's BlockSpec index_map
reads ``table[b, j]`` and the DMA engine streams the PHYSICAL page
straight into the online-softmax accumulation — no logical view ever
exists in HBM.

Schedule.  The tuned ``block_s`` (a multiple of the table's
``page_block``) still sets the sweep granularity, exactly as in
``decode_attention``; a ``block_s`` chunk just cannot be one contiguous
DMA anymore (its pages are scattered), so the grid splits each chunk
into its ``block_s / page_block`` pages:

    grid = (B, ceil(T/block_s), block_s/page_block)

with running (m, l, acc) scratch carried across the whole (step, page)
sweep of one row.  ``block_s`` therefore changes the lowered grid
structure — the decision the tuner makes — never the math.

The blocked reference (``paged_decode_attention_ref``) honours the same
schedule: a ``lax.scan`` over ``block_s`` windows, each window gathering
only its own pages via ``paged_flat_indices`` — no full-cache
materialization, and it additionally supports the traced sliding-window
masks the Pallas path declines.

Unmapped table entries (-1: a retired slot, or the tail of a short
lease) clamp to physical block 0; every position they could contribute
is masked by ``cache_len``, so they are never *read* meaningfully — the
same contract as ``paged_gather``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hw import TpuParams, ceil_div
from repro.core.mapper import MappingPolicy
from repro.kernels.decode_attention import plan_cache_block
from repro.kernels.paged_gather import paged_flat_indices

__all__ = ["plan_paged_block", "paged_decode_attention",
           "paged_decode_attention_pallas", "paged_decode_attention_ref"]

_NEG_INF = float("-inf")


def plan_paged_block(s: int, d: int, page_block: int, hw: TpuParams,
                     policy: MappingPolicy, dtype_bytes: int) -> int:
    """Eq. 1 seed for the fused sweep's ``block_s``, legalized onto the
    table geometry: the cache-block plan of ``decode_attention``,
    quantized DOWN to a ``page_block`` multiple (a sweep chunk is a whole
    number of physical pages) and clamped to the padded cache length.

    Example::

        >>> from repro.core.hw import TPU_REGISTRY
        >>> plan_paged_block(256, 64, 16, TPU_REGISTRY["cpu_sim"],
        ...                  MappingPolicy.TUNED, 4) % 16
        0
    """
    base = plan_cache_block(s, d, hw, policy, dtype_bytes)
    bs = max(page_block, base // page_block * page_block)
    return min(bs, ceil_div(s, page_block) * page_block)


# --------------------------------------------------------------------------- #
# Blocked reference — the same schedule, per-window gathers only
# --------------------------------------------------------------------------- #


def paged_decode_attention_ref(
    q: jax.Array,                 # (B, G, R, D) — one new token
    k_cache: jax.Array,           # (B, T, G, D) — PHYSICAL block grid
    v_cache: jax.Array,
    tables: jax.Array,            # (B, nb) int32, -1 = unmapped
    cache_len,                    # scalar or (B,)
    *,
    page_block: int,
    block_s: int,
    window=None,                  # int | traced scalar | None
    scale=None,
    k_scale=None,                 # (B, T/pb, G) f32 — int8 pool only
    v_scale=None,
) -> jax.Array:
    """Blocked fused reference: sweeps the LOGICAL sequence in
    ``block_s`` windows, each window gathering only its own physical
    pages through the table — the fused kernel's schedule without
    Pallas, and the numerics oracle for it.

    With ``k_scale``/``v_scale`` the caches hold int8 codes on the same
    physical grid and dequant happens per window: each window gathers
    its pages' (block, head) scales by the SAME flat block index the
    codes use (``flat_token // page_block``), so no dequantized cache is
    ever materialized — the schedule the fused int8 kernel executes.

    Example::

        o = paged_decode_attention_ref(q, kc, vc, tables, clen,
                                       page_block=16, block_s=64)
    """
    b, t = k_cache.shape[:2]
    g, r, d = q.shape[1:]
    scale = scale if scale is not None else d ** -0.5
    block_s = max(page_block, min(int(block_s), ceil_div(t, page_block)
                                  * page_block))
    nb = ceil_div(t, page_block)
    idx = paged_flat_indices(tables[:, :nb], b, t, page_block)   # (B, T)
    tp = ceil_div(t, block_s) * block_s
    if tp != t:
        # padded positions clamp to flat index 0; every one of them is
        # >= t >= cache_len, so the mask below zeroes their scores
        idx = jnp.pad(idx, ((0, 0), (0, tp - t)))
    n = tp // block_s
    idx = jnp.moveaxis(idx.reshape(b, n, block_s), 1, 0)         # (n, B, bs)
    quant = k_scale is not None
    if quant:
        assert t % page_block == 0, (t, page_block)
        kf = k_cache.reshape((b * t,) + k_cache.shape[2:])
        vf = v_cache.reshape((b * t,) + v_cache.shape[2:])
        ksf = k_scale.reshape(b * nb, g)
        vsf = v_scale.reshape(b * nb, g)
    else:
        kf = k_cache.astype(jnp.float32).reshape((b * t,)
                                                 + k_cache.shape[2:])
        vf = v_cache.astype(jnp.float32).reshape((b * t,)
                                                 + v_cache.shape[2:])
    qf = q.astype(jnp.float32) * scale
    clen = jnp.asarray(cache_len)
    clen = clen[:, None] if clen.ndim else clen[None, None]      # (B|1, 1)

    def step(carry, xs):
        m, l, acc = carry
        ix, ci = xs                                              # (B, bs)
        kb = jnp.take(kf, ix.reshape(-1), axis=0).reshape(b, block_s, g, d)
        vb = jnp.take(vf, ix.reshape(-1), axis=0).reshape(b, block_s, g, d)
        if quant:
            # flat_token // pb == flat block index: codes and scales
            # resolve through one layout invariant
            bix = (ix // page_block).reshape(-1)
            sk = jnp.take(ksf, bix, axis=0).reshape(b, block_s, g)
            sv = jnp.take(vsf, bix, axis=0).reshape(b, block_s, g)
            kb = kb.astype(jnp.float32) * sk[..., None]
            vb = vb.astype(jnp.float32) * sv[..., None]
        s = jnp.einsum("bgrd,bcgd->bgrc", qf, kb)
        pos = ci * block_s + jnp.arange(block_s)[None, :]        # (1, bs)
        ok = pos < clen
        if window is not None:
            ok &= pos > clen - 1 - window
        s = jnp.where(ok[:, None, None, :], s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, -1))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.where(jnp.isfinite(s), jnp.exp(s - m_safe[..., None]), 0.0)
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_new = l * alpha + jnp.sum(p, -1)
        acc_new = acc * alpha[..., None] \
            + jnp.einsum("bgrc,bcgd->bgrd", p, vb)
        return (m_new, l_new, acc_new), None

    init = (jnp.full((b, g, r), _NEG_INF, jnp.float32),
            jnp.zeros((b, g, r), jnp.float32),
            jnp.zeros((b, g, r, d), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(step, init, (idx, jnp.arange(n)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


# --------------------------------------------------------------------------- #
# Pallas kernel — scalar-prefetched table drives the k/v index_map
# --------------------------------------------------------------------------- #


def _sweep_page(len_ref, q_ref, k, v, o_ref, m_ref, l_ref, acc_ref, *,
                page_block: int, ppb: int, scale: float):
    """One physical page's online-softmax update — the shared body of
    the fp32 and int8 kernels (which differ only in how ``k``/``v`` were
    produced from their refs).  ``len_ref`` is the scalar-prefetched
    (B,) cache-length vector in SMEM: a (1,)-block VMEM operand over it
    is refused by the TPU tiling rules."""
    clen = len_ref[pl.program_id(0)]
    si = pl.program_id(1)
    pi = pl.program_id(2)

    @pl.when((si == 0) & (pi == 0))
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale            # (G, R, D)
    s = jnp.einsum("grd,cgd->grc", q, k,
                   preferred_element_type=jnp.float32)  # (G, R, pb)
    pos = (si * ppb + pi) * page_block \
        + jax.lax.broadcasted_iota(jnp.int32, (1, 1, page_block), 2)
    s = jnp.where(pos < clen, s, _NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.where(jnp.isfinite(s), jnp.exp(s - m_safe[..., None]), 0.0)
    alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
    m_ref[...] = m_new
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[..., None] + jnp.einsum(
        "grc,cgd->grd", p, v,
        preferred_element_type=jnp.float32)

    @pl.when((si == pl.num_programs(1) - 1) & (pi == pl.num_programs(2) - 1))
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[..., None]).astype(o_ref.dtype)


def _paged_decode_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                         m_ref, l_ref, acc_ref, *,
                         page_block: int, ppb: int, scale: float):
    del tbl_ref            # consumed by the index_map, not the body
    _sweep_page(len_ref, q_ref, k_ref[0].astype(jnp.float32),
                v_ref[0].astype(jnp.float32), o_ref, m_ref, l_ref,
                acc_ref, page_block=page_block, ppb=ppb, scale=scale)


def _paged_decode_kernel_int8(tbl_ref, len_ref, q_ref, k_ref, v_ref,
                              ks_ref, vs_ref, o_ref,
                              m_ref, l_ref, acc_ref, *,
                              page_block: int, ppb: int, scale: float):
    # the (1, 1, G) scale rows rode the SAME scalar-prefetched flat-block
    # index as the int8 pages; dequant is in-register, per page — the
    # f32 view never exists outside this grid step
    del tbl_ref
    k = k_ref[0].astype(jnp.float32) * ks_ref[0, 0][None, :, None]
    v = v_ref[0].astype(jnp.float32) * vs_ref[0, 0][None, :, None]
    _sweep_page(len_ref, q_ref, k, v, o_ref, m_ref, l_ref, acc_ref,
                page_block=page_block, ppb=ppb, scale=scale)


def paged_decode_attention_pallas(
    q: jax.Array,                 # (B, G, R, D)
    k_cache: jax.Array,           # (B, T, G, D) — PHYSICAL block grid
    v_cache: jax.Array,
    tables: jax.Array,            # (B, nb) int32, -1 = unmapped
    cache_len,                    # scalar or (B,)
    *,
    page_block: int,
    block_s: int,
    scale=None,
    k_scale=None,                 # (B, T/pb, G) f32 — int8 pool only
    v_scale=None,
    interpret: bool = False,
) -> jax.Array:
    """The fused kernel: grid (B, T/block_s, block_s/page_block), the
    scalar-prefetched flat-block table routing ONE physical page per
    innermost grid step straight into the online softmax — decode reads
    paged KV with zero intermediate materialization.  The cache lengths
    are the second scalar-prefetch operand.  With ``k_scale``/``v_scale``
    the caches hold int8 codes; the scales are two extra (1, 1, G)
    BlockSpec inputs over a (B*nb, 1, G) view (the last two block dims
    whole, as the TPU tiling requires) riding the SAME prefetched table
    entry as their page, dequantized in-register inside the sweep.

    Example::

        o = paged_decode_attention_pallas(q, kc, vc, tables, clen,
                                          page_block=16, block_s=64,
                                          interpret=True)
    """
    b, t = k_cache.shape[:2]
    g, r, d = q.shape[1:]
    pb = int(page_block)
    if t % pb or block_s % pb or block_s < pb:
        raise ValueError(
            f"fused paged decode needs whole pages: cache length {t} and "
            f"block_s {block_s} must be multiples of page_block {pb}")
    scale = scale if scale is not None else d ** -0.5
    nb = t // pb
    ppb = min(block_s // pb, nb)
    nsteps = ceil_div(nb, ppb)
    # physical pid -> flat block index over the (B*nb, pb, G, D) reshape
    # (column-major pool grid: row = pid % B, offset-block = pid // B)
    pid = jnp.maximum(tables[:, :nb], 0).astype(jnp.int32)
    flat_block = (pid % b) * nb + (pid // b)                     # (B, nb)
    if nsteps * ppb != nb:
        # tail pages alias block 0; their positions are >= T >= cache_len
        flat_block = jnp.pad(flat_block, ((0, 0), (0, nsteps * ppb - nb)))
    blocks_k = k_cache.reshape(b * nb, pb, g, d)
    blocks_v = v_cache.reshape(b * nb, pb, g, d)
    clen = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (b,))
    quant = k_scale is not None

    page_spec = pl.BlockSpec((1, pb, g, d),
                             lambda bi, si, pi, tbl, lens:
                             (tbl[bi, si * ppb + pi], 0, 0, 0))
    scale_spec = pl.BlockSpec((1, 1, g),
                              lambda bi, si, pi, tbl, lens:
                              (tbl[bi, si * ppb + pi], 0, 0))
    in_specs = [
        pl.BlockSpec((1, g, r, d),
                     lambda bi, si, pi, tbl, lens: (bi, 0, 0, 0)),
        page_spec,
        page_spec,
    ]
    operands = [q, blocks_k, blocks_v]
    kernel = _paged_decode_kernel
    if quant:
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale.reshape(b * nb, 1, g),
                     v_scale.reshape(b * nb, 1, g)]
        kernel = _paged_decode_kernel_int8

    out = pl.pallas_call(
        functools.partial(kernel, page_block=pb, ppb=ppb, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, nsteps, ppb),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, g, r, d),
                                   lambda bi, si, pi, tbl, lens:
                                   (bi, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g, r), jnp.float32),
                pltpu.VMEM((g, r), jnp.float32),
                pltpu.VMEM((g, r, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, g, r, d), q.dtype),
        interpret=interpret,
    )(flat_block, clen, *operands)
    return out


def paged_decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    tables: jax.Array,
    cache_len,
    *,
    page_block: int,
    block_s: int,
    window=None,
    scale=None,
    k_scale=None,
    v_scale=None,
    use_pallas: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """Dispatch the fused paged sweep: the Pallas kernel when requested,
    the blocked reference with the same schedule otherwise.  Sliding
    windows always take the reference (the kernel masks only cache
    length); a requested kernel on an illegal geometry (a cache or
    ``block_s`` that is not whole pages) raises ``ValueError`` rather
    than running the reference in its place.  ``k_scale``/``v_scale``
    select the int8 dequant-fused variants on both paths.

    Example::

        o = paged_decode_attention(q, kc, vc, tables, clen,
                                   page_block=16, block_s=64)
    """
    if use_pallas and window is None:
        return paged_decode_attention_pallas(
            q, k_cache, v_cache, tables, cache_len, page_block=page_block,
            block_s=block_s, scale=scale, k_scale=k_scale,
            v_scale=v_scale, interpret=interpret)
    return paged_decode_attention_ref(
        q, k_cache, v_cache, tables, cache_len, page_block=page_block,
        block_s=block_s, window=window, scale=scale, k_scale=k_scale,
        v_scale=v_scale)
