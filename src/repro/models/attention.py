"""Grouped-query attention: training/prefill (chunked-flash dataflow) and
decode (split-KV; GSPMD distributes the sharded-cache reductions).

GQA is computed *grouped* — q reshaped to (B, S, G, R, D) against
k/v (B, T, G, D) — never materializing repeated KV heads.  On TPU the
per-head hot loop dispatches to the Pallas flash kernel (kernels/ops); on
other platforms the lax.scan chunked form below keeps the same O(S·chunk)
working set so dry-run HLO bytes stay faithful to the fused kernel.

Mask model: ``causal`` + optional sliding ``window`` + optional
``prefix_len`` (prefix-LM bidirectionality for the VLM) — all expressed as
position predicates so they compose.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import ParamSpec, ShardCtx, apply_rope, rmsnorm

_NEG = float("-inf")


def attention_specs(cfg: ModelConfig) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    s = {
        "wq": ParamSpec((d, h, hd), ("embed", "heads", "head_dim")),
        "wk": ParamSpec((d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wv": ParamSpec((d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wo": ParamSpec((h, hd, d), ("heads", "head_dim", "embed")),
    }
    if cfg.qk_norm:
        s["q_norm"] = ParamSpec((hd,), (None,), init="zeros")
        s["k_norm"] = ParamSpec((hd,), (None,), init="zeros")
    return s


def _mask(q_pos, k_pos, *, causal: bool, window: Optional[int],
          prefix_len) -> jax.Array:
    """q_pos (..., Sq, 1), k_pos (..., 1, Sk) -> bool allowed."""
    ok = jnp.ones(jnp.broadcast_shapes(q_pos.shape, k_pos.shape), bool)
    if causal:
        ok &= k_pos <= q_pos
    if window is not None:
        ok &= k_pos > q_pos - window
    if prefix_len is not None:
        ok |= k_pos < prefix_len          # everyone sees the whole prefix
    return ok


def _mask_dyn(q_pos, k_pos, *, causal: bool, window, prefix,
              kstart=None) -> jax.Array:
    """Dynamic-parameter mask: window/prefix/kstart are traced f32 scalars
    (window = +inf -> no window; prefix = -1 -> no prefix; kstart masks
    keys below it — used by banded attention for edge-block padding)."""
    ok = jnp.ones(jnp.broadcast_shapes(q_pos.shape, k_pos.shape), bool)
    if causal:
        ok &= k_pos <= q_pos
    ok &= k_pos.astype(jnp.float32) > q_pos.astype(jnp.float32) - window
    ok |= k_pos.astype(jnp.float32) < prefix
    if kstart is not None:
        ok &= k_pos.astype(jnp.float32) >= kstart
    return ok


@functools.lru_cache(maxsize=None)
def _make_flash(causal: bool, chunk: int, q_offset: int, scale: float):
    """Flash attention with a custom VJP.

    Without this, the bwd of the lax.scan chunked form would stash the
    running (m, l, acc) carry per KV chunk — O(S^2/chunk) memory.  The
    custom bwd recomputes the probabilities per chunk from the saved
    (q, k, v, out, lse) — O(S) residuals, ~2.5x fwd FLOPs, the standard
    flash-attention backward."""

    @jax.custom_vjp
    def flash(q, k, v, window, prefix, kstart):
        out, _ = _flash_fwd(q, k, v, window, prefix, kstart)
        return out

    def _flash_fwd(q, k, v, window, prefix, kstart):
        b, sq, g, r, d = q.shape
        sk = k.shape[1]
        n = sk // chunk
        qf = q.astype(jnp.float32) * scale
        kc = jnp.moveaxis(k.astype(jnp.float32).reshape(b, n, chunk, g, d), 1, 0)
        vc = jnp.moveaxis(v.astype(jnp.float32).reshape(b, n, chunk, g, d), 1, 0)
        q_pos = jnp.arange(sq) + q_offset

        def step(carry, xs):
            m, l, acc = carry
            kb, vb, ci = xs
            s = jnp.einsum("bsgrd,bcgd->bsgrc", qf, kb)
            k_pos = ci * chunk + jnp.arange(chunk)
            ok = _mask_dyn(q_pos[:, None], k_pos[None, :], causal=causal,
                           window=window, prefix=prefix, kstart=kstart)
            s = jnp.where(ok[None, :, None, None, :], s, _NEG)
            m_new = jnp.maximum(m, jnp.max(s, -1))
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - m_safe[..., None])
            p = jnp.where(jnp.isfinite(s), p, 0.0)
            alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
            l_new = l * alpha + jnp.sum(p, -1)
            acc_new = acc * alpha[..., None] \
                + jnp.einsum("bsgrc,bcgd->bsgrd", p, vb)
            return (m_new, l_new, acc_new), None

        init = (jnp.full((b, sq, g, r), _NEG, jnp.float32),
                jnp.zeros((b, sq, g, r), jnp.float32),
                jnp.zeros((b, sq, g, r, d), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(step, init, (kc, vc, jnp.arange(n)))
        l_safe = jnp.maximum(l, 1e-30)
        out = (acc / l_safe[..., None]).astype(q.dtype)
        lse = jnp.where(jnp.isfinite(m), m + jnp.log(l_safe), _NEG)
        return out, lse

    def fwd(q, k, v, window, prefix, kstart):
        out, lse = _flash_fwd(q, k, v, window, prefix, kstart)
        return out, (q, k, v, out, lse, window, prefix, kstart)

    def bwd(res, do):
        q, k, v, out, lse, window, prefix, kstart = res
        b, sq, g, r, d = q.shape
        sk = k.shape[1]
        n = sk // chunk
        qf = q.astype(jnp.float32) * scale
        dof = do.astype(jnp.float32)
        kc = jnp.moveaxis(k.astype(jnp.float32).reshape(b, n, chunk, g, d), 1, 0)
        vc = jnp.moveaxis(v.astype(jnp.float32).reshape(b, n, chunk, g, d), 1, 0)
        q_pos = jnp.arange(sq) + q_offset
        delta = jnp.sum(dof * out.astype(jnp.float32), -1)       # (B,S,G,R)
        lse_safe = jnp.where(jnp.isfinite(lse), lse, 0.0)

        def step(dq, xs):
            kb, vb, ci = xs
            s = jnp.einsum("bsgrd,bcgd->bsgrc", qf, kb)
            k_pos = ci * chunk + jnp.arange(chunk)
            ok = _mask_dyn(q_pos[:, None], k_pos[None, :], causal=causal,
                           window=window, prefix=prefix, kstart=kstart)
            s = jnp.where(ok[None, :, None, None, :], s, _NEG)
            p = jnp.where(jnp.isfinite(s),
                          jnp.exp(s - lse_safe[..., None]), 0.0)
            dv = jnp.einsum("bsgrc,bsgrd->bcgd", p, dof)
            dp = jnp.einsum("bsgrd,bcgd->bsgrc", dof, vb)
            ds = p * (dp - delta[..., None])
            dq = dq + jnp.einsum("bsgrc,bcgd->bsgrd", ds, kb) * scale
            dk = jnp.einsum("bsgrc,bsgrd->bcgd", ds, qf)
            return dq, (dk, dv)

        dq0 = jnp.zeros((b, sq, g, r, d), jnp.float32)
        dq, (dks, dvs) = jax.lax.scan(step, dq0, (kc, vc, jnp.arange(n)))
        dk = jnp.moveaxis(dks, 0, 1).reshape(b, sk, g, d)
        dv = jnp.moveaxis(dvs, 0, 1).reshape(b, sk, g, d)
        return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
                jnp.zeros_like(window), jnp.zeros_like(prefix),
                jnp.zeros_like(kstart))

    flash.defvjp(fwd, bwd)
    return flash


def chunked_attention(
    q: jax.Array,                 # (B, Sq, G, R, D)
    k: jax.Array,                 # (B, Sk, G, D)
    v: jax.Array,                 # (B, Sk, G, D)
    *,
    causal: bool = True,
    window=None,                  # int | traced scalar | None
    prefix_len=None,              # int | None
    q_offset: int = 0,
    scale: Optional[float] = None,
    chunk: int = 512,
) -> jax.Array:
    """Flash-structured grouped attention; returns (B, Sq, G, R, D)."""
    d = q.shape[-1]
    sk = k.shape[1]
    scale = scale if scale is not None else d ** -0.5
    chunk = min(chunk, sk)
    while sk % chunk:
        chunk //= 2
    win = jnp.asarray(window if window is not None else jnp.inf, jnp.float32)
    pre = jnp.asarray(prefix_len if prefix_len is not None else -1.0,
                      jnp.float32)
    fn = _make_flash(causal, chunk, q_offset, float(scale))
    return fn(q, k, v, win, pre, jnp.float32(-jnp.inf))


def banded_attention(
    q: jax.Array,                 # (B, S, G, R, D)
    k: jax.Array,                 # (B, S, G, D)
    v: jax.Array,
    *,
    window: int,
    band: Optional[int] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Exact causal sliding-window attention in O(S·band) instead of the
    masked full O(S^2) sweep — the §Perf lever for local:global archs.

    Queries are tiled into bands; each band attends only to (previous
    band, own band), which is exact whenever ``window <= band``.  The
    band size is the lws analogue over key positions: the temporal extent
    one query block sweeps."""
    b, s, g, r, d = q.shape
    band = band or window
    assert window <= band, (window, band)
    while s % band:
        band //= 2
    assert window <= band, "sequence too short for the requested band"
    nb = s // band
    qb = q.reshape(b, nb, band, g, r, d)
    kb = k.reshape(b, nb, band, g, d)
    vb = v.reshape(b, nb, band, g, d)
    prev = lambda x: jnp.pad(x, ((0, 0), (1, 0)) + ((0, 0),) * (x.ndim - 2)
                             )[:, :-1]
    k2 = jnp.concatenate([prev(kb), kb], axis=2)     # (B, nb, 2*band, G, D)
    v2 = jnp.concatenate([prev(vb), vb], axis=2)
    # block 0's "previous band" is padding: mask keys below kstart=band
    kstart = jnp.where(jnp.arange(nb) == 0, float(band), -jnp.inf
                       ).astype(jnp.float32)
    sc = scale if scale is not None else d ** -0.5
    fn = _make_flash(True, min(512, 2 * band), band, float(sc))
    out = jax.vmap(
        lambda qi, ki, vi, ks: fn(qi, ki, vi, jnp.float32(window),
                                  jnp.float32(-1.0), ks),
        in_axes=(1, 1, 1, 0), out_axes=1)(qb, k2, v2, kstart)
    return out.reshape(b, s, g, r, d)


def triangular_attention(
    q: jax.Array,                 # (B, S, G, R, D)
    k: jax.Array,                 # (B, S, G, D)
    v: jax.Array,
    *,
    chunk: int = 512,
    scale: Optional[float] = None,
) -> jax.Array:
    """Causal attention with TRIANGULAR chunk scheduling — forward only.

    The masked-full sweep computes nb^2 chunk products; causality only
    needs nb(nb+1)/2.  Sequential q blocks (lax.scan, NOT vmap — vmap
    would batch the cond into a select and defeat the skip) each scan the
    kv chunks with a ``lax.cond`` that skips future chunks at runtime.
    Used for PREFILL (no grads flow; training keeps the custom-VJP flash
    path).  §Perf lever, exactness pinned by tests."""
    b, s, g, r, d = q.shape
    scale = scale if scale is not None else d ** -0.5
    chunk = min(chunk, s)
    while s % chunk:
        chunk //= 2
    nb = s // chunk
    qr = q.astype(jnp.float32).reshape(b, nb, chunk, g, r, d) * scale
    kc = k.astype(jnp.float32).reshape(b, nb, chunk, g, d)
    vc = v.astype(jnp.float32).reshape(b, nb, chunk, g, d)

    def q_block(_, qi):
        qb = jax.lax.dynamic_index_in_dim(qr, qi, 1, keepdims=False)

        def kv_step(st, ci):
            def compute(st):
                m, l, acc = st
                kb = jax.lax.dynamic_index_in_dim(kc, ci, 1, keepdims=False)
                vb = jax.lax.dynamic_index_in_dim(vc, ci, 1, keepdims=False)
                sc = jnp.einsum("bsgrd,bcgd->bsgrc", qb, kb)
                qp = qi * chunk + jnp.arange(chunk)[:, None]
                kp = ci * chunk + jnp.arange(chunk)[None, :]
                sc = jnp.where((kp <= qp)[None, :, None, None, :], sc, _NEG)
                m_new = jnp.maximum(m, jnp.max(sc, -1))
                m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
                p = jnp.where(jnp.isfinite(sc),
                              jnp.exp(sc - m_safe[..., None]), 0.0)
                alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
                return (m_new, l * alpha + jnp.sum(p, -1),
                        acc * alpha[..., None]
                        + jnp.einsum("bsgrc,bcgd->bsgrd", p, vb))

            st = jax.lax.cond(ci <= qi, compute, lambda st: st, st)
            return st, None

        init = (jnp.full((b, chunk, g, r), _NEG, jnp.float32),
                jnp.zeros((b, chunk, g, r), jnp.float32),
                jnp.zeros((b, chunk, g, r, d), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(kv_step, init, jnp.arange(nb))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return 0, out.astype(q.dtype)

    _, outs = jax.lax.scan(q_block, 0, jnp.arange(nb))
    # outs (nb, B, chunk, G, R, D) -> (B, S, G, R, D)
    return jnp.moveaxis(outs, 0, 1).reshape(b, s, g, r, d)


def tiled_prefill_attention(
    q: jax.Array,                 # (B, Sq, G, R, D)
    k: jax.Array,                 # (B, Sk, G, D)
    v: jax.Array,
    *,
    block_q: int,
    block_k: int,
    causal: bool = True,
    window=None,                  # int | traced scalar | None
    prefix_len=None,              # int | None
    q_offset: int = 0,
    scale: Optional[float] = None,
) -> jax.Array:
    """Blocked prefill flash sweep EXECUTING the tuned (block_q, block_k).

    The reference realization of the bucket-resolved prefill mapping on
    platforms without the Pallas kernel: queries are tiled into
    ``block_q`` rows (outer ``lax.scan``) and keys into ``block_k``
    columns (inner scan with running online-softmax stats), so both tile
    decisions change the lowered loop structure — the grid the tuner
    decided — while the math is the flash recurrence, identical to
    ``chunked_attention``.  Forward-only (prefill; training keeps the
    custom-VJP flash path)."""
    b, s, g, r, d = q.shape
    sk = k.shape[1]
    scale = scale if scale is not None else d ** -0.5
    bq = max(1, min(int(block_q), s))
    bk = max(1, min(int(block_k), sk))
    sp, skp = -(-s // bq) * bq, -(-sk // bk) * bk
    if sp != s:
        q = jnp.pad(q, ((0, 0), (0, sp - s)) + ((0, 0),) * 3)
    if skp != sk:
        pad = ((0, 0), (0, skp - sk), (0, 0), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    nq, nk = sp // bq, skp // bk
    win = jnp.asarray(window if window is not None else jnp.inf, jnp.float32)
    pre = jnp.asarray(prefix_len if prefix_len is not None else -1.0,
                      jnp.float32)
    qf = q.astype(jnp.float32).reshape(b, nq, bq, g, r, d) * scale
    kc = jnp.moveaxis(k.astype(jnp.float32).reshape(b, nk, bk, g, d), 1, 0)
    vc = jnp.moveaxis(v.astype(jnp.float32).reshape(b, nk, bk, g, d), 1, 0)

    def q_block(_, qi):
        qb = jax.lax.dynamic_index_in_dim(qf, qi, 1, keepdims=False)
        q_pos = qi * bq + jnp.arange(bq) + q_offset

        def kv_step(carry, xs):
            m, l, acc = carry
            kb, vb, ci = xs
            sc = jnp.einsum("bsgrd,bcgd->bsgrc", qb, kb)
            k_pos = ci * bk + jnp.arange(bk)
            ok = _mask_dyn(q_pos[:, None], k_pos[None, :], causal=causal,
                           window=win, prefix=pre)
            ok &= (k_pos < sk)[None, :]            # key-padding columns
            sc = jnp.where(ok[None, :, None, None, :], sc, _NEG)
            m_new = jnp.maximum(m, jnp.max(sc, -1))
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.where(jnp.isfinite(sc),
                          jnp.exp(sc - m_safe[..., None]), 0.0)
            alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
            return (m_new, l * alpha + jnp.sum(p, -1),
                    acc * alpha[..., None]
                    + jnp.einsum("bsgrc,bcgd->bsgrd", p, vb)), None

        init = (jnp.full((b, bq, g, r), _NEG, jnp.float32),
                jnp.zeros((b, bq, g, r), jnp.float32),
                jnp.zeros((b, bq, g, r, d), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(kv_step, init,
                                      (kc, vc, jnp.arange(nk)))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return 0, out.astype(q.dtype)

    _, outs = jax.lax.scan(q_block, 0, jnp.arange(nq))
    out = jnp.moveaxis(outs, 0, 1).reshape(b, sp, g, r, d)
    return out[:, :s] if sp != s else out


def pallas_prefill_attention(
    q: jax.Array,                 # (B, S, G, R, D)
    k: jax.Array,                 # (B, S, G, D)
    v: jax.Array,
    *,
    block_q: int,
    block_k: int,
    causal: bool = True,
    scale: Optional[float] = None,
    q_offset=0,                   # int | traced scalar: query 0's position
    interpret: bool = False,
) -> jax.Array:
    """Run the Pallas flash kernel with the tuned (block_q, block_k) over
    the grouped prefill layout: one kernel instance per (batch, kv-group,
    q-head) row, the K/V rows shared across a group's R q-heads — the
    executed form of the serving router's per-bucket prefill plan.
    ``q_offset`` places a prompt chunk's queries inside a longer key
    cache (chunked prefill's traced start)."""
    from repro.core.hw import detect
    from repro.core.mapper import MappingPolicy, attention_plan_for_blocks
    from repro.kernels.flash_attention import flash_attention_pallas

    hw = detect()
    s, d = q.shape[1], q.shape[-1]
    plan = attention_plan_for_blocks(s, k.shape[1], d, hw, int(block_q),
                                     int(block_k), MappingPolicy.TUNED,
                                     dtype_bytes=q.dtype.itemsize)
    qt = q.transpose(0, 2, 3, 1, 4)                       # (B, G, R, S, D)
    kt = jnp.moveaxis(k, 2, 1)                            # (B, G, S, D)
    vt = jnp.moveaxis(v, 2, 1)

    def one(q_row, k_row, v_row):
        return flash_attention_pallas(q_row, k_row, v_row, hw=hw,
                                      causal=causal, scale=scale, plan=plan,
                                      q_offset=q_offset, interpret=interpret)

    per_r = jax.vmap(one, in_axes=(0, None, None))        # R (K/V shared)
    per_g = jax.vmap(per_r, in_axes=(0, 0, 0))            # G
    per_b = jax.vmap(per_g, in_axes=(0, 0, 0))            # B
    out = per_b(qt, kt, vt)                               # (B, G, R, S, D)
    return out.transpose(0, 3, 1, 2, 4)


def decode_attention_grouped(
    q: jax.Array,                 # (B, G, R, D) — one new token
    k_cache: jax.Array,           # (B, T, G, D)
    v_cache: jax.Array,
    cache_len,                    # scalar or (B,)
    *,
    window: Optional[int] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Split-KV decode: scores over the full (possibly seq-sharded) cache.

    Expressed as plain einsum + masked softmax so GSPMD turns the
    reductions over a sharded T into partial-reduce + all-reduce — the
    distributed flash-decode of DESIGN.md §5 (long_500k cells)."""
    d = q.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    s = jnp.einsum("bgrd,btgd->bgrt", q.astype(jnp.float32) * scale,
                   k_cache.astype(jnp.float32))
    t = k_cache.shape[1]
    pos = jnp.arange(t)
    clen = jnp.asarray(cache_len)
    clen = clen[:, None, None, None] if clen.ndim else clen
    ok = pos < clen
    if window is not None:
        ok &= pos > clen - 1 - window
    s = jnp.where(ok, s, _NEG)
    m = jnp.max(s, -1, keepdims=True)
    p = jnp.exp(s - jax.lax.stop_gradient(m))
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    out = jnp.einsum("bgrt,btgd->bgrd", p, v_cache.astype(jnp.float32))
    out = out / jnp.maximum(p.sum(-1), 1e-30)[..., None]
    return out.astype(q.dtype)


def blocked_decode_attention(
    q: jax.Array,                 # (B, G, R, D) — one new token
    k_cache: jax.Array,           # (B, T, G, D)
    v_cache: jax.Array,
    cache_len,                    # scalar or (B,)
    *,
    block: int,
    window=None,                  # int | traced scalar | None
    scale: Optional[float] = None,
) -> jax.Array:
    """Split-KV decode that sweeps the cache in ``block``-sized chunks
    with an online softmax — the reference execution of the tuned
    ``decode_block`` mapping (kernels/decode_attention's schedule) on
    platforms without the Pallas kernel.  ``block`` changes the lowered
    loop structure (the grid the tuner decided), never the math."""
    b, g, r, d = q.shape
    t = k_cache.shape[1]
    scale = scale if scale is not None else d ** -0.5
    block = max(1, min(int(block), t))
    tp = -(-t // block) * block
    pad = ((0, 0), (0, tp - t), (0, 0), (0, 0))
    kc = jnp.pad(k_cache, pad) if tp != t else k_cache
    vc = jnp.pad(v_cache, pad) if tp != t else v_cache
    n = tp // block
    kc = jnp.moveaxis(kc.astype(jnp.float32).reshape(b, n, block, g, d), 1, 0)
    vc = jnp.moveaxis(vc.astype(jnp.float32).reshape(b, n, block, g, d), 1, 0)
    qf = q.astype(jnp.float32) * scale
    clen = jnp.asarray(cache_len)
    clen = clen[:, None] if clen.ndim else clen[None, None]      # (B|1, 1)

    def step(carry, xs):
        m, l, acc = carry
        kb, vb, ci = xs
        s = jnp.einsum("bgrd,bcgd->bgrc", qf, kb)
        pos = ci * block + jnp.arange(block)[None, :]            # (1, block)
        ok = pos < clen
        if window is not None:
            ok &= pos > clen - 1 - window
        s = jnp.where(ok[:, None, None, :], s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, -1))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.where(jnp.isfinite(s), jnp.exp(s - m_safe[..., None]), 0.0)
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_new = l * alpha + jnp.sum(p, -1)
        acc_new = acc * alpha[..., None] \
            + jnp.einsum("bgrc,bcgd->bgrd", p, vb)
        return (m_new, l_new, acc_new), None

    init = (jnp.full((b, g, r), _NEG, jnp.float32),
            jnp.zeros((b, g, r), jnp.float32),
            jnp.zeros((b, g, r, d), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(step, init, (kc, vc, jnp.arange(n)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def pallas_decode_attention(
    q: jax.Array,                 # (B, G, R, D)
    k_cache: jax.Array,           # (B, T, G, D)
    v_cache: jax.Array,
    cache_len: jax.Array,         # (B,)
    *,
    block: int,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    """Run the Pallas flash-decode kernel with the tuned ``block`` over
    the grouped cache layout: one kernel instance per (batch, kv-group,
    q-head) row, the cache block shared across the R q-heads of a group."""
    from repro.core.hw import detect
    from repro.kernels import decode_attention as _dak

    hw = detect()
    kt = jnp.moveaxis(k_cache, 2, 1)                  # (B, G, T, D)
    vt = jnp.moveaxis(v_cache, 2, 1)

    def one(q_row, k_row, v_row, clen):
        return _dak.decode_attention_pallas(
            q_row, k_row, v_row, clen, hw=hw, scale=scale,
            block_s=int(block), interpret=interpret)

    per_r = jax.vmap(one, in_axes=(0, None, None, None))    # R (cache shared)
    per_g = jax.vmap(per_r, in_axes=(0, 0, 0, None))        # G
    per_b = jax.vmap(per_g, in_axes=(0, 0, 0, 0))           # B
    return per_b(q, kt, vt, cache_len)


# --------------------------------------------------------------------------- #
# Full attention block
# --------------------------------------------------------------------------- #


def _project_qkv(params, x, cfg: ModelConfig, cos, sin, ctx: ShardCtx):
    """Project q/k/v into the grouped layout (B, S, G, R, D) / (B, S, G, D).

    The GQA sharding regime is resolved at runtime (runtime.sharding):
      * ``kv_heads % tp == 0`` — grouped: shard the G (kv group) axis;
      * else if ``heads % tp == 0`` — ``expand_kv``: repeat KV to full
        heads, shard the (now G=H, R=1) head axis.  Per device this holds
        H/tp KV head copies — *less* memory than replicating all kv_heads
        and avoids split-sharded reshapes (no GSPMD resharding thrash);
      * else — replicated attention (small models only).
    """
    b, s, _ = x.shape
    expand = bool(ctx.flag("expand_kv", False))
    g = max(cfg.num_kv_heads, 1)
    r = cfg.num_heads // g
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dgk->bsgk", x, params["wk"])
    v = jnp.einsum("bsd,dgk->bsgk", x, params["wv"])
    if cfg.qk_norm:
        q = rmsnorm(q, params["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, params["k_norm"], cfg.norm_eps)
    if cos is not None:
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    if expand:
        k = jnp.repeat(k, r, axis=2)
        v = jnp.repeat(v, r, axis=2)
        g, r = cfg.num_heads, 1
        kv_axis = "heads"
    else:
        kv_axis = "kv_heads"
    q = q.reshape(b, s, g, r, cfg.head_dim)
    q = ctx.p(q, "batch", None, kv_axis, None, None)
    k = ctx.p(k, "batch", None, kv_axis, None)
    v = ctx.p(v, "batch", None, kv_axis, None)
    return q, k, v


def attention_block(
    params: dict,
    x: jax.Array,                 # (B, S, D)
    cfg: ModelConfig,
    *,
    cos=None,
    sin=None,
    causal: bool = True,
    window=None,
    prefix_len=None,
    q_offset: int = 0,
    kv_override: Optional[tuple[jax.Array, jax.Array]] = None,
    banded: bool = False,
    prefill_tiles: Optional[tuple[int, int]] = None,
    ctx: ShardCtx,
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """Returns (output (B,S,D), (k, v) for caching).

    ``prefill_tiles`` is the bucket-tuned flash (block_q, block_k)
    resolved by the serving router: when given, the attention EXECUTES
    at that mapping — the Pallas flash kernel where available, otherwise
    the tile-honouring blocked reference sweep.  ``None`` keeps the
    hardware-agnostic GSPMD path (training and non-serving callers)."""
    b, s, _ = x.shape
    q, k, v = _project_qkv(params, x, cfg, cos, sin, ctx)
    if kv_override is not None:
        k, v = kv_override
    if banded:
        o = banded_attention(q, k, v, window=int(window))
    elif prefill_tiles is not None and kv_override is None:
        bq, bk = prefill_tiles
        use_pallas, interpret = _pallas_mode()
        if (use_pallas and causal and window is None
                and prefix_len is None and q_offset == 0):
            o = pallas_prefill_attention(q, k, v, block_q=bq, block_k=bk,
                                         causal=causal, interpret=interpret)
        else:
            # dynamic windows / prefix-LM masks stay on the reference
            # sweep, which honours the same tile schedule
            o = tiled_prefill_attention(q, k, v, block_q=bq, block_k=bk,
                                        causal=causal, window=window,
                                        prefix_len=prefix_len,
                                        q_offset=q_offset)
    elif (ctx.flag("triangular_causal", False) and causal
          and window is None and prefix_len is None and q_offset == 0
          and kv_override is None):
        # prefill-only flop skip (fwd-only; train keeps the custom VJP)
        o = triangular_attention(q, k, v)
    else:
        o = chunked_attention(q, k, v, causal=causal, window=window,
                              prefix_len=prefix_len, q_offset=q_offset)
    kv_axis = "heads" if ctx.flag("expand_kv", False) else "kv_heads"
    o = ctx.p(o, "batch", None, kv_axis, None, None)
    out = jnp.einsum("bshk,hkd->bsd", o.reshape(b, s, -1, cfg.head_dim),
                     params["wo"])
    return out, (k, v)


#: fixed-point scale for int8 KV caches.  Per-tensor k/v scales fold into
#: the q/out projections at deployment (standard KV-quant trick), so a
#: single constant is exact at the lowering level and ~1% error numerically
#: for unit-variance caches.
KV_INT8_SCALE = 32.0


def _cache_write(cache, new, pos, *, page_tables=None, page_block=None):
    """Write one new (B, G, D) KV row at per-row positions ``pos``.

    With ``page_tables`` (B, nb) the write is PHYSICAL: each row's
    position routes through its block table to a scatter at the leased
    block's flat offset (``kernels.paged_gather`` documents the pid ->
    location mapping), and rows whose table entry is unmapped (-1 — a
    retired slot) or whose position overruns the table write NOTHING
    (out-of-range scatter indices drop), so recycled blocks are never
    touched by their previous tenant."""
    if cache.dtype == jnp.int8:
        new = jnp.clip(jnp.round(new.astype(jnp.float32) * KV_INT8_SCALE),
                       -127, 127)
    new = new.astype(cache.dtype)
    pos = jnp.asarray(pos)
    if page_tables is not None:
        from repro.kernels.paged_gather import flat_position

        b, t = cache.shape[:2]
        bs = int(page_block)
        nb = page_tables.shape[1]
        new = new[:, 0] if new.ndim == cache.ndim else new   # drop S=1 axis
        pos = jnp.broadcast_to(pos, (b,)) if pos.ndim == 0 else pos
        bi = jnp.clip(pos // bs, 0, nb - 1)
        pid = page_tables[jnp.arange(b), bi]                  # (B,)
        valid = (pid >= 0) & (pos // bs < nb) & (pos < t)
        flat = flat_position(pid, pos, b, t, bs)
        flat = jnp.where(valid, flat, b * t)      # OOB scatter index: drop
        flat_cache = cache.reshape((b * t,) + cache.shape[2:])
        flat_cache = flat_cache.at[flat].set(new, mode="drop")
        return flat_cache.reshape(cache.shape)
    if pos.ndim == 1:
        # ragged pool (serving): each row writes at its OWN position.  A
        # one-hot select instead of per-row dynamic slices keeps the write
        # a single fused op; rows whose pos >= T write nothing (the mask
        # never fires), so retired slots are inert until re-admitted.
        t = cache.shape[1]
        row = jnp.arange(t)[None, :] == pos[:, None]          # (B, T)
        return jnp.where(row[..., None, None], new, cache)
    return jax.lax.dynamic_update_slice_in_dim(cache, new, pos, axis=1)


def _chunk_cache_write(cache, new, start):
    """Write one C-row prompt chunk at positions ``start..start+C-1``.

    A scatter, NOT ``dynamic_update_slice``: a radix-resumed chunk's
    ``start`` is the match's resume position, which need not be
    chunk-aligned, so the slab may overhang the cache row —
    ``dynamic_update_slice`` would CLAMP the start back inside and
    silently clobber the seeded prefix rows.  Overhanging rows here
    carry only the tail chunk's padding garbage; dropping them is the
    contract (``chunk_prefill_step``'s docstring)."""
    idx = start + jnp.arange(new.shape[1])
    return cache.at[:, idx].set(new.astype(cache.dtype), mode="drop")


def _cache_read(cache, compute_dtype):
    if cache.dtype == jnp.int8:
        return (cache.astype(jnp.float32) / KV_INT8_SCALE
                ).astype(compute_dtype)
    return cache


def _paged_quant_write(cache, scale, new, pos, *, page_tables, page_block):
    """Write one (B, G, D) KV row into the int8 paged pool, maintaining
    the per-(physical block, kv head) symmetric scales.

    Requantize-on-scale-growth: the block's scale only ever grows
    (``new_scale = max(old, amax(|token|)/127)``), and when it grows the
    block's existing codes are rescaled by ``old/new_scale`` in the same
    scatter.  ``scale == 0`` is the DEAD sentinel — ``write_row`` zeroes
    every leased block's scale beyond the prompt, so the first decode
    write into a fresh (or recycled) block sees ``old == 0``, rescales
    the stale tenant's codes by 0, and sets the scale from its own amax:
    a recycled block can never leak codes *or* scales across tenants.
    Rows whose table entry is unmapped or whose position overruns drop
    both scatters, matching ``_cache_write``'s retired-row contract."""
    b, t = cache.shape[:2]
    bs = int(page_block)
    nb_t = t // bs
    nb = page_tables.shape[1]
    new = new[:, 0] if new.ndim == cache.ndim else new       # (B, G, D)
    pos = jnp.asarray(pos)
    pos = jnp.broadcast_to(pos, (b,)) if pos.ndim == 0 else pos
    bi = jnp.clip(pos // bs, 0, nb - 1)
    pid = page_tables[jnp.arange(b), bi]                     # (B,)
    valid = (pid >= 0) & (pos // bs < nb) & (pos < t)
    pidc = jnp.maximum(pid, 0)
    row, off = pidc % b, pidc // b                           # physical grid
    old = scale[row, off]                                    # (B, G)
    amax = jnp.max(jnp.abs(new.astype(jnp.float32)), axis=-1)
    new_scale = jnp.maximum(old, amax / 127.0)
    safe = jnp.where(new_scale > 0, new_scale, 1.0)
    ratio = jnp.where(new_scale > 0, old / safe, 0.0)        # 0 wipes stale
    flat_cache = cache.reshape((b * t,) + cache.shape[2:])
    idx = (row * t + off * bs)[:, None] + jnp.arange(bs)[None, :]
    codes = jnp.take(flat_cache, idx.reshape(-1), axis=0) \
        .reshape((b, bs) + cache.shape[2:])                  # (B, bs, G, D)
    codes = jnp.round(codes.astype(jnp.float32) * ratio[:, None, :, None])
    tok = jnp.round(new.astype(jnp.float32) / safe[..., None])
    hot = jnp.arange(bs)[None, :] == (pos % bs)[:, None]     # (B, bs)
    codes = jnp.where(hot[..., None, None], tok[:, None], codes)
    codes = jnp.clip(codes, -127, 127).astype(cache.dtype)
    idx = jnp.where(valid[:, None], idx, b * t)   # OOB scatter index: drop
    flat_cache = flat_cache.at[idx.reshape(-1)].set(
        codes.reshape((-1,) + cache.shape[2:]), mode="drop")
    sflat = scale.reshape((b * nb_t,) + scale.shape[2:])
    sidx = jnp.where(valid, row * nb_t + off, b * nb_t)
    sflat = sflat.at[sidx].set(new_scale.astype(scale.dtype), mode="drop")
    return (flat_cache.reshape(cache.shape),
            sflat.reshape(scale.shape))


def attention_decode(
    params: dict,
    x: jax.Array,                 # (B, 1, D)
    cfg: ModelConfig,
    k_cache: jax.Array,           # (B, T, G, D) — model dtype or int8
    v_cache: jax.Array,
    pos,                          # current position: scalar or (B,) ragged
    *,
    cos=None,
    sin=None,
    window: Optional[int] = None,
    decode_block: Optional[int] = None,
    page_tables=None,             # (B, nb) int32 | None — physical paging
    page_block: Optional[int] = None,
    paged_decode_block: Optional[int] = None,
    k_scale=None,                 # (B, T/pb, G) f32 | None — int8 pool
    v_scale=None,
    ctx: ShardCtx,
) -> tuple[jax.Array, tuple[jax.Array, ...]]:
    """One-token decode; returns (out (B,1,D), updated caches).

    ``k_scale``/``v_scale`` mark the QUANTIZED paged pool: the caches
    hold int8 codes, writes go through the requantize-on-scale-growth
    scatter (``_paged_quant_write``), reads dequantize INSIDE the fused
    sweep (or the gather kernel on the ablation path) — no f32 cache is
    ever materialized — and the return carries the updated scales:
    ``(out, (k_cache, v_cache, k_scale, v_scale))``.  When they are
    ``None`` (the default) this function traces the exact pre-quantized
    graph, keeping the fp32 serving path byte-identical.

    A vector ``pos`` (B,) drives the ragged serving pool: every row
    writes its new KV at its own position and masks its own cache
    length, so mixed-progress requests share one compiled step.

    ``decode_block`` is the bucket-tuned cache block resolved by the
    serving router (``serve.buckets`` via ``tuner.resolve_plan``): when
    given, the attention sweep EXECUTES at that mapping — the Pallas
    flash-decode kernel where available, otherwise the blocked reference
    sweep with the same schedule.  ``None`` keeps the plain einsum path
    (GSPMD-distributable; the non-serving callers).

    ``page_tables`` switches the cache to PHYSICAL paging: the (B, T)
    arrays become a block grid and writes scatter through each row's
    block table.  With ``paged_decode_block`` (the router's tuned fused
    ``block_s``) the sweep CONSUMES the tables directly — the fused
    ``kernels.paged_decode_attention`` streams physical pages with no
    materialized logical view.  Without it the read falls back to
    gather-then-sweep (Pallas gather kernel on TPU, ``jnp.take``
    reference elsewhere); either way slot recycling re-points blocks
    instead of copying cache rows."""
    b = x.shape[0]
    q, k, v = _project_qkv(params, x, cfg, cos, sin, ctx)
    if k_scale is not None:
        return _attention_decode_quantized(
            params, q, k, v, cfg, k_cache, v_cache, k_scale, v_scale,
            pos, window=window, decode_block=decode_block,
            page_tables=page_tables, page_block=page_block,
            paged_decode_block=paged_decode_block,
            compute_dtype=x.dtype)
    # write the new kv at position `pos` (quantizing if the cache is int8)
    k_cache = _cache_write(k_cache, k, pos, page_tables=page_tables,
                           page_block=page_block)
    v_cache = _cache_write(v_cache, v, pos, page_tables=page_tables,
                           page_block=page_block)
    kr = _cache_read(k_cache, x.dtype)
    vr = _cache_read(v_cache, x.dtype)
    if page_tables is not None and paged_decode_block is not None:
        # fused path: the block table rides into the kernel as a data
        # operand (scalar-prefetched on the Pallas path), so the paged
        # cache is read exactly once — no logical-view round-trip
        from repro.kernels.paged_decode_attention import \
            paged_decode_attention

        use_pallas, interpret = _pallas_mode()
        clen = jnp.broadcast_to(jnp.asarray(pos + 1, jnp.int32), (b,))
        o = paged_decode_attention(
            q[:, 0], kr, vr, page_tables, clen,
            page_block=int(page_block), block_s=int(paged_decode_block),
            window=window, use_pallas=use_pallas, interpret=interpret)
        out = jnp.einsum("bhk,hkd->bd", o.reshape(b, -1, cfg.head_dim),
                         params["wo"])
        return out[:, None, :], (k_cache, v_cache)
    if page_tables is not None:
        from repro.kernels.paged_gather import paged_gather

        use_pallas, interpret = _pallas_mode()
        kr = paged_gather(kr, page_tables, int(page_block),
                          use_pallas=use_pallas, interpret=interpret)
        vr = paged_gather(vr, page_tables, int(page_block),
                          use_pallas=use_pallas, interpret=interpret)
    clen = pos + 1
    if decode_block is None:
        o = decode_attention_grouped(q[:, 0], kr, vr, clen, window=window)
    else:
        use_pallas, interpret = _pallas_mode()
        if use_pallas and window is None:
            clen_v = jnp.broadcast_to(jnp.asarray(clen, jnp.int32), (b,))
            o = pallas_decode_attention(q[:, 0], kr, vr, clen_v,
                                        block=decode_block,
                                        interpret=interpret)
        else:
            # sliding windows (traced per layer) stay on the reference
            # sweep: the kernel masks only cache length
            o = blocked_decode_attention(q[:, 0], kr, vr, clen,
                                         block=decode_block, window=window)
    out = jnp.einsum("bhk,hkd->bd", o.reshape(b, -1, cfg.head_dim),
                     params["wo"])
    return out[:, None, :], (k_cache, v_cache)


def _attention_decode_quantized(
    params, q, k, v, cfg, k_cache, v_cache, k_scale, v_scale, pos, *,
    window, decode_block, page_tables, page_block, paged_decode_block,
    compute_dtype,
):
    """The int8 paged-pool decode: quantizing scatter writes, then a
    read that dequantizes inside the executed kernel — the fused
    table-consuming sweep when ``paged_decode_block`` is tuned, the
    dequant-fused gather + dense sweep on the ablation path."""
    assert page_tables is not None, "kv scales require the paged pool"
    b = q.shape[0]
    k_cache, k_scale = _paged_quant_write(k_cache, k_scale, k, pos,
                                          page_tables=page_tables,
                                          page_block=page_block)
    v_cache, v_scale = _paged_quant_write(v_cache, v_scale, v, pos,
                                          page_tables=page_tables,
                                          page_block=page_block)
    use_pallas, interpret = _pallas_mode()
    if paged_decode_block is not None:
        from repro.kernels.paged_decode_attention import \
            paged_decode_attention

        clen = jnp.broadcast_to(jnp.asarray(pos + 1, jnp.int32), (b,))
        o = paged_decode_attention(
            q[:, 0], k_cache, v_cache, page_tables, clen,
            page_block=int(page_block), block_s=int(paged_decode_block),
            window=window, k_scale=k_scale, v_scale=v_scale,
            use_pallas=use_pallas, interpret=interpret)
    else:
        from repro.kernels.paged_gather import paged_dequant_gather

        kr = paged_dequant_gather(k_cache, k_scale, page_tables,
                                  int(page_block), out_dtype=compute_dtype,
                                  use_pallas=use_pallas,
                                  interpret=interpret)
        vr = paged_dequant_gather(v_cache, v_scale, page_tables,
                                  int(page_block), out_dtype=compute_dtype,
                                  use_pallas=use_pallas,
                                  interpret=interpret)
        clen = pos + 1
        if decode_block is None:
            o = decode_attention_grouped(q[:, 0], kr, vr, clen,
                                         window=window)
        elif use_pallas and window is None:
            clen_v = jnp.broadcast_to(jnp.asarray(clen, jnp.int32), (b,))
            o = pallas_decode_attention(q[:, 0], kr, vr, clen_v,
                                        block=decode_block,
                                        interpret=interpret)
        else:
            o = blocked_decode_attention(q[:, 0], kr, vr, clen,
                                         block=decode_block, window=window)
    out = jnp.einsum("bhk,hkd->bd", o.reshape(b, -1, cfg.head_dim),
                     params["wo"])
    return out[:, None, :], (k_cache, v_cache, k_scale, v_scale)


def _pallas_mode() -> tuple[bool, bool]:
    """(use_pallas, interpret) from the process-wide kernel force mode —
    the same switch every ``kernels.ops`` entry point obeys."""
    from repro.kernels.ops import _use_pallas
    return _use_pallas()
