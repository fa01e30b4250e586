"""Fused table-consuming paged flash decode: only live pages are read.

``paged_gather`` + ``decode_attention`` pays one full extra HBM round
trip per decode step: the block-table gather materializes a logical KV
view that the flash sweep immediately re-reads.  This kernel fuses the
indirection into the sweep: the pool stays in HBM (``pl.ANY``) and the
kernel copies the physical pages a row can see straight into VMEM,
through a flat block table that rides as scalar prefetch.  No logical
view ever exists in HBM.

Schedule.  The tuned ``block_s`` (a whole number of the table's
``page_block``) sets the grid:

    grid = (B, ceil(nb / ppb)),   ppb = block_s / page_block

one step per ``block_s`` chunk of one row.  Which pages are live is
decided from the kernel's own inputs: page ``p`` of row ``b`` is live
when ``p * page_block < cache_len[b]`` and ``tables[b, p] >= 0``, and a
row sees its leading live pages (``_live_lengths``).  A chunk past them
neither copies nor computes; a row with none (a retired slot, whose
table is all -1 while its position keeps advancing) writes zeros.
Inside a step the chunk's live pages stream through a double-buffered
VMEM tile in sub-blocks of at most ``_SUB_POSITIONS`` positions, one
online-softmax update (f32 ``m``, ``l``, ``acc``) per sub-block; the
copies of the next sub-block (of this chunk, of this row's next chunk,
or of the next live row's first) run while this one computes.  So VMEM
is bounded whatever ``block_s`` is, ``block_s`` changes the lowered grid
(the decision the tuner makes) and never the math, and the work follows
the live context, not the pool.

Layout.  A page is read as ``(pb*G*D/W, W)`` rows of ``W = lcm(D, 128)``
lanes: the DMA engine slices the pool only along tile-aligned minor
dims, which ``(pb, G, D)`` with G*D = 192 are not.
Each ``W``-lane row holds ``W/D`` head-rows; the query is laid out once
per lane block, so one ``(G*R, W) x (n, W)`` product per lane block
scores every head, and a constant position map masks each score to its
own head.  The int8 pool shares the sweep body: each layer's
per-(page, head) scales sit whole in SMEM and multiply the scores and
probabilities of their pages.

The blocked reference (``paged_decode_attention_ref``) honours the same
``block_s`` schedule: a ``lax.scan`` over ``block_s`` windows, each
window gathering only its own pages via ``paged_flat_indices``.  It
reads unmapped entries (-1) as physical block 0 and masks them by
``cache_len``, and additionally supports the traced sliding-window
masks the Pallas path declines.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
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
# Pallas kernel — one grid step per block_s chunk, live pages only
# --------------------------------------------------------------------------- #

#: positions per fetched sub-block: what one online-softmax update covers,
#: and what bounds the kernel's VMEM whatever ``block_s`` is
_SUB_POSITIONS = 512


def _live_lengths(tables, clen, nb: int, pb: int):
    """(B,) positions each row can see: its cache length, cut at the
    first page that lies past it or is unmapped (-1).  A retired row
    (all -1, its position still advancing) sees nothing."""
    page = jnp.arange(nb, dtype=jnp.int32)
    live = (page[None, :] * pb < clen[:, None]) & (tables[:, :nb] >= 0)
    n_live = jnp.sum(jnp.cumprod(live.astype(jnp.int32), axis=1), axis=1)
    return jnp.minimum(clen, n_live * pb)


def _next_live_row(lim):
    """(B,) the first row after each row with something to see, B if
    none: where a row's last chunk prefetches the next one's first."""
    b = lim.shape[0]
    rows = jnp.where(lim > 0, jnp.arange(b, dtype=jnp.int32), b)
    first_from = jax.lax.cummin(rows, reverse=True)
    return jnp.concatenate([first_from[1:], jnp.full((1,), b, jnp.int32)])


def _head_positions(sub: int, rpp: int, w: int, g: int, r: int, d: int):
    """(W/D, G*R, sub*rpp) int32: for lane block ``j`` of tile row ``u``
    (head-row ``h = u*W/D + j`` of the sub-block, i.e. position
    ``h // G``, head ``h % G``), the position within the sub-block where
    query row ``gi*R + ri`` has head ``gi`` there, else -1."""
    k = w // d
    u = np.arange(sub * rpp)
    out = np.full((k, g * r, sub * rpp), -1, np.int32)
    for j in range(k):
        h = u * k + j
        for gi in range(g):
            out[j, gi * r:(gi + 1) * r] = np.where(h % g == gi, h // g, -1)
    return jnp.asarray(out)


def _scale_cols(s_ref, flat_ref, row0, base, rpp: int, sub: int, g: int,
                r: int, last):
    """(G*R, sub*rpp) per-(page, head) dequant scales of one sub-block in
    score layout: query row ``gi*R + ri`` against tile row ``u`` holds
    head ``gi``'s scale on the sub-block's page ``u // rpp``.  Read as
    scalars from the layer's whole (B*nb*G,) scale vector in SMEM."""
    n = sub * rpp
    col = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (g * r, n), 0)
    heads = []
    for gi in range(g):
        h = jnp.zeros((1, n), jnp.float32)
        for i in range(sub):
            page = flat_ref[row0 + jnp.minimum(base + i, last)]
            h = jnp.where((col >= i * rpp) & (col < (i + 1) * rpp),
                          s_ref[page * g + gi], h)
        heads.append(h)
    out = heads[-1]
    for gi in range(g - 2, -1, -1):
        out = jnp.where(row < (gi + 1) * r, heads[gi], out)
    return out


def _paged_decode_kernel(flat_ref, lim_ref, nxt_ref, q_ref, pat_ref, k_hbm,
                         v_hbm, *rest, pb: int, ppb: int, sub: int, nbp: int,
                         rpp: int, w: int, g: int, r: int, d: int,
                         quant: bool):
    """One grid step = one ``block_s`` chunk of one row.  The chunk's
    live pages stream through a double-buffered VMEM tile in sub-blocks
    of ``sub`` pages; each sub-block is one online-softmax update.  The
    copies of the next sub-block (of this chunk, this row's next chunk
    or the next live row's first) are in flight while this one computes.
    The bf16 and int8 pools share this body: int8 multiplies the scores
    and the probabilities by the pages' per-head scales."""
    if quant:
        ks_ref, vs_ref, o_ref, kbuf, vbuf, sem, m_ref, l_ref, acc_ref, \
            state = rest
    else:
        o_ref, kbuf, vbuf, sem, m_ref, l_ref, acc_ref, state = rest
    bi, ci = pl.program_id(0), pl.program_id(1)
    n = sub * rpp                   # tile rows of one sub-block
    k = w // d                      # head-rows per tile row

    def n_live(row):
        return (lim_ref[row] + pb - 1) // pb

    def page_count(row, base):
        return jnp.minimum(jnp.minimum(sub, (base // ppb + 1) * ppb - base),
                           n_live(row) - base)

    def page_copies(row, base, slot, fn):
        def one(i, carry):
            page = flat_ref[row * nbp + base + i]
            fn(pltpu.make_async_copy(k_hbm.at[page], kbuf.at[slot, i],
                                     sem.at[0, slot]))
            fn(pltpu.make_async_copy(v_hbm.at[page], vbuf.at[slot, i],
                                     sem.at[1, slot]))
            return carry

        jax.lax.fori_loop(0, page_count(row, base), one, 0)

    @pl.when(ci == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

        @pl.when(bi == 0)
        def _first():
            state[0] = 0           # the slot the next sub-block lands in
            state[1] = 0           # whether it has been started

    nl = n_live(bi)
    base0 = ci * ppb

    @pl.when(base0 < nl)
    def _sweep():
        @pl.when(state[1] == 0)
        def _prime():
            page_copies(bi, base0, state[0], lambda c: c.start())
            state[1] = 1

        lim = lim_ref[bi]
        nxt = nxt_ref[bi]
        more_chunks = base0 + ppb < nl
        nsub = (jnp.minimum(nl - base0, ppb) + sub - 1) // sub
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)

        def unit(j, slot):
            base = base0 + j * sub
            in_chunk = j + 1 < nsub

            @pl.when(in_chunk | more_chunks | (nxt < pl.num_programs(0)))
            def _prefetch():
                row = jnp.where(in_chunk | more_chunks, bi, nxt)
                nbase = jnp.where(in_chunk, base + sub,
                                  jnp.where(more_chunks, base0 + ppb, 0))
                page_copies(row, nbase, 1 - slot, lambda c: c.start())

            page_copies(bi, base, slot, lambda c: c.wait())
            kt = kbuf[slot].astype(jnp.float32).reshape(n, w)
            vt = vbuf[slot].astype(jnp.float32).reshape(n, w)
            # tile rows past the fetched pages hold stale VMEM: zero them
            fetched = page_count(bi, base) * rpp
            vt = jnp.where(
                jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0) < fetched,
                vt, 0.0)
            seen = lim - base * pb          # visible positions from here
            if quant:
                ksc = _scale_cols(ks_ref, flat_ref, bi * nbp, base, rpp,
                                  sub, g, r, nbp - 1)
                vsc = _scale_cols(vs_ref, flat_ref, bi * nbp, base, rpp,
                                  sub, g, r, nbp - 1)
            ss, oks = [], []
            for hj in range(k):
                s = jax.lax.dot_general(q_ref[0, hj], kt,
                                        (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32)
                if quant:
                    s = s * ksc
                at = pat_ref[hj]
                ok = (at >= 0) & (at < seen)                # (G*R, n)
                ss.append(jnp.where(ok, s, _NEG_INF))
                oks.append(ok)
            m_prev = m_ref[...]
            # a sub-block always holds a visible position: m_new is finite
            m_new = m_prev
            for s in ss:
                m_new = jnp.maximum(m_new, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_ref[...] * alpha
            pv = jnp.zeros(acc_ref.shape, jnp.float32)
            for hj, (s, ok) in enumerate(zip(ss, oks)):
                p = jnp.exp(s - m_new)
                l_new = l_new + jnp.sum(p, axis=1, keepdims=True)
                if quant:
                    p = jnp.where(ok, p * vsc, 0.0)
                pv = jnp.where((lane >= hj * d) & (lane < (hj + 1) * d),
                               jnp.dot(p, vt,
                                       preferred_element_type=jnp.float32),
                               pv)
            m_ref[...] = m_new
            l_ref[...] = l_new
            acc_ref[...] = acc_ref[...] * alpha + pv
            return 1 - slot

        state[0] = jax.lax.fori_loop(0, nsub, unit, state[0])

    @pl.when(ci == pl.num_programs(1) - 1)
    def _flush():
        # lane block j of query row gi*R + ri sums head gi's values of
        # the head-rows at block j; a row that saw nothing stays 0
        acc = acc_ref[...]
        out = acc[:, :d]
        for hj in range(1, k):
            out = out + acc[:, hj * d:(hj + 1) * d]
        out = out / jnp.maximum(l_ref[...], 1e-30)
        for gi in range(g):
            o_ref[0, gi] = out[gi * r:(gi + 1) * r].astype(o_ref.dtype)


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
    """The fused kernel: grid (B, ceil(nb / (block_s/page_block))), one
    step per ``block_s`` chunk of one row.  The pool stays in HBM; a
    step copies only the chunk's live pages (leading mapped pages below
    the row's cache length) into VMEM, through the flat-block table,
    live lengths and next-live-row vector that ride as scalar prefetch.
    Chunks past a row's live pages neither copy nor compute, and a row
    with none writes zeros.  With ``k_scale``/``v_scale`` the caches
    hold int8 codes, and each layer's scales sit whole in SMEM.

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
    # a page is read as rows of w lanes: the DMA engine slices the pool
    # only along tile-aligned minor dims, which (pb, G, D) are not
    w = math.lcm(d, 128)
    if (pb * g * d) % w:
        raise ValueError(
            f"fused paged decode reads a page as rows of {w} lanes: "
            f"page_block*kv_heads*head_dim = {pb * g * d} is not a "
            f"multiple of it")
    scale = scale if scale is not None else d ** -0.5
    nb = t // pb
    ppb = min(block_s // pb, nb)
    nsteps = ceil_div(nb, ppb)
    nbp = nsteps * ppb
    sub = min(ppb, max(1, _SUB_POSITIONS // pb))
    rpp = pb * g * d // w
    k = w // d
    # physical pid -> flat block index over the (B*nb, rpp, W) view
    # (column-major pool grid: row = pid % B, offset-block = pid // B)
    pid = jnp.maximum(tables[:, :nb], 0).astype(jnp.int32)
    flat_block = (pid % b) * nb + (pid // b)                     # (B, nb)
    flat_block = jnp.pad(flat_block, ((0, 0), (0, nbp - nb))).reshape(-1)
    clen = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (b,))
    lim = _live_lengths(tables, clen, nb, pb)
    # query row gi*R + ri, scaled, on lane block j of copy j: one
    # (G*R, W) x (n, W) product per block scores every head at once
    qx = jnp.einsum("bgrd,jh->bjgrhd", q.astype(jnp.float32) * scale,
                    jnp.eye(k, dtype=jnp.float32)).reshape(b, k, g * r, w)
    quant = k_scale is not None

    in_specs = [pl.BlockSpec((1, k, g * r, w),
                             lambda bi, ci, *_: (bi, 0, 0, 0)),
                pl.BlockSpec((k, g * r, sub * rpp),
                             lambda bi, ci, *_: (0, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY)]
    operands = [qx, _head_positions(sub, rpp, w, g, r, d),
                k_cache.reshape(b * nb, rpp, w),
                v_cache.reshape(b * nb, rpp, w)]
    if quant:
        in_specs += [pl.BlockSpec(memory_space=pltpu.SMEM)] * 2
        operands += [k_scale.reshape(-1), v_scale.reshape(-1)]

    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, pb=pb, ppb=ppb, sub=sub,
                          nbp=nbp, rpp=rpp, w=w, g=g, r=r, d=d, quant=quant),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, nsteps),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, g, r, d),
                                   lambda bi, ci, *_: (bi, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, sub, rpp, w), k_cache.dtype),
                pltpu.VMEM((2, sub, rpp, w), v_cache.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((g * r, 1), jnp.float32),
                pltpu.VMEM((g * r, 1), jnp.float32),
                pltpu.VMEM((g * r, w), jnp.float32),
                pltpu.SMEM((2,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, g, r, d), q.dtype),
        # the prefetch chain runs across rows: one core, in grid order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="paged_decode_attention",
    )(flat_block, lim, _next_live_row(lim), *operands)
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
