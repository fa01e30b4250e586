"""CacheAdapter — the family-generic face of the ragged decode pool.

The serving engine keeps one physical cache pytree for the whole pool
(slots rows, one request per row) and needs five operations on it, none
of which should know what family it is serving:

  ``init_pool``       build the pool cache with a per-row ``pos`` vector
  ``prefill_len``     how long to pad a prompt before prefill
  ``prefill_extras``  family-specific prefill inputs (encoder frames)
  ``write_row``       scatter one prefilled request's cache into a slot
  ``grow``            pad the pool's length-bearing arrays to a bucket

``CacheAdapter`` is that protocol; ``FamilyCacheAdapter`` implements it
once, generically, because every family's decode cache is a dict of
layer-leading arrays ``(L, batch, ...)`` plus ``pos`` — the families
differ only in *which* keys carry a time axis to pad and whether prompt
padding is safe:

  dense/moe   k/v (L, B, T, G, hd): time axis grows with the bucket;
  hybrid      k/v per attention group + position-free ssm state/conv;
  encdec      self-attention k/v grow; cross ck/cv are static per row;
  ssm         state/conv only — nothing carries a time axis, the pool
              "grows" in block accounting alone;
  vlm         dense k/v, but every cache position is SHIFTED by the
              config's ``prefix_tokens`` image-patch positions — the
              adapter's ``position_offset`` is that shift, and all of
              the pool's capacity/page/position math adds it.

Prompt padding: attention caches mask per-row length, so right-padding a
prompt to its bucket never leaks — but a *recurrent* state after the
padded tail is contaminated (there is no mask on a carried state), so
the ssm adapter prefills at the exact prompt length instead
(``prefill_buckets=False``).  Hybrid prefill seeds its ssm states at
zero (see ``models.model.Model.prefill``), so only its masked attention
caches carry prompt content and bucketing stays safe.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Protocol

import jax
import jax.numpy as jnp

__all__ = ["CacheAdapter", "FamilyCacheAdapter", "ADAPTERS", "get_adapter"]


class CacheAdapter(Protocol):
    """What the engine (and the accounting layer under it) asks of a
    family's decode-cache state.  Implementations must be pure: every
    mutator returns a new cache pytree.

    Example::

        adapter = get_adapter(cfg.family)
        cache = adapter.init_pool(model, slots=4, kv_len=64)
        cache = adapter.write_row(cache, slot, row_cache, plen, kv_len)
    """

    family: str
    #: keys whose arrays carry the pool's time axis (L, B, T, ...) and
    #: must pad when the length bucket steps up; empty for recurrent
    #: caches, in which case pool growth is block accounting only and
    #: the compiled decode shape never changes with kv_len
    length_keys: tuple[str, ...]
    #: False — prefill at the exact prompt length (recurrent state is
    #: exact only at the sequence end; no mask can hide padded steps)
    prefill_buckets: bool

    def init_pool(self, model: Any, slots: int, kv_len: int, *,
                  expand_kv: bool = False) -> dict:
        """Build the pool cache with a per-row ``pos`` vector."""
        ...

    def prefill_len(self, prompt_len: int,
                    quantize: Callable[[int], int]) -> int:
        """The length a prompt pads to before prefill (bucket or exact)."""
        ...

    def prefill_extras(self, model: Any, rows: int) -> dict:
        """Family-specific prefill inputs (e.g. encoder frames)."""
        ...

    def write_row(self, cache: dict, slot: int, row_cache: dict,
                  prompt_len: int, kv_len: int,
                  page_map: Optional[Any] = None) -> dict:
        """Scatter one prefilled request's cache into its leased slot
        (through ``page_map`` when the pool is physically paged)."""
        ...

    def grow(self, cache: dict, new_len: int) -> dict:
        """Pad the pool's length-bearing arrays to a new bucket."""
        ...

    @property
    def grows_with_len(self) -> bool:
        """False for recurrent caches: growth is accounting-only."""
        ...


@dataclasses.dataclass(frozen=True)
class FamilyCacheAdapter:
    """Generic ``CacheAdapter`` over dict-of-(L, batch, ...) caches.

    One implementation serves every family because the families differ
    only in *which* keys carry a time axis (``length_keys``) and whether
    prompt padding is safe (``prefill_buckets`` — see module docstring).

    Example::

        ssm = FamilyCacheAdapter("ssm", length_keys=(),
                                 prefill_buckets=False)
    """

    family: str
    length_keys: tuple[str, ...] = ("k", "v")
    prefill_buckets: bool = True
    extras: Optional[Callable[[Any, int], dict]] = None
    #: cache positions a request occupies before its first token (the
    #: vlm prefix patches); ``None`` means 0 for every model
    prefix_offset: Optional[Callable[[Any], int]] = None
    #: True when the family's whole per-position sequence state lives in
    #: the paged k/v blocks, so a prompt prefix cached by one request is
    #: complete context for another (radix prefix sharing) — attention
    #: caches with no carried recurrent state and chunked prefill
    shareable_prefix: bool = False

    @property
    def grows_with_len(self) -> bool:
        return bool(self.length_keys)

    def position_offset(self, model: Any) -> int:
        """Cache positions before token 0 for this model (vlm: the
        image-patch prefix ``cfg.prefix_tokens``; 0 elsewhere).

        Example::

            >>> get_adapter("dense").position_offset(None)
            0
        """
        return self.prefix_offset(model) if self.prefix_offset else 0

    def init_pool(self, model, slots: int, kv_len: int, *,
                  expand_kv: bool = False, kv_dtype: str = "fp32",
                  block_size: int = 16) -> dict:
        """The family's decode cache with a per-row (ragged) ``pos``.

        ``kv_dtype="int8"`` allocates the length-bearing keys as int8
        codes and adds a per-(physical block, kv head) f32 scale array
        per key (``k_scale``/``v_scale``, shaped ``(L, slots, kv_len /
        block_size, G)``), initialized to the ZERO dead-block sentinel —
        no block carries a meaningful scale until a tenant writes one."""
        from repro.core.dtypes import kv_dtype_spec

        spec = kv_dtype_spec(kv_dtype)
        quantize = spec.quantized and bool(self.length_keys)
        cache = model.init_cache(
            slots, kv_len, expand_kv=expand_kv,
            cache_dtype=spec.dtype if quantize else None)
        if quantize:
            for key in self.length_keys:
                arr = cache[key]                    # (L, B, T, G, hd)
                cache[key + "_scale"] = jnp.zeros(
                    arr.shape[:2] + (kv_len // block_size, arr.shape[3]),
                    jnp.float32)
        cache["pos"] = jnp.zeros((slots,), jnp.int32)   # per-row, ragged
        return cache

    def prefill_len(self, prompt_len: int, quantize) -> int:
        """Prompt bucket when masking makes padding safe, else exact."""
        return quantize(prompt_len) if self.prefill_buckets else prompt_len

    def prefill_extras(self, model, rows: int) -> dict:
        """Extra prefill batch entries (``{}`` for most families)."""
        return self.extras(model, rows) if self.extras else {}

    def write_row(self, cache: dict, slot: int, row_cache: dict,
                  prompt_len: int, kv_len: int, page_map=None,
                  scale_map=None, page_block=None, start: int = 0) -> dict:
        """Scatter a single-row prefill cache into the pool at ``slot``.
        Length-bearing keys are right-padded from the prompt bucket to
        the pool row; everything else (recurrent states, cross KV) lands
        shape-exact.  The row's ``pos`` becomes the true prompt length —
        the mask/rope boundary, regardless of padding.

        ``page_map`` (prompt_len - start,) — flat physical positions
        from the request's block table — with ``page_block`` switches
        the length-bearing keys to the PAGED write: only the prompt's
        own tokens land in the leased blocks (no full-row copy, no tail
        padding; positions past the prompt are masked by ``pos`` until
        decode overwrites them).

        Every write is a ``dynamic_update_slice`` (one per block on the
        paged path), so under ``jit`` with the pool donated the row
        lands in place, in whatever layout the pool has.

        ``start`` (block-aligned, paged-only) begins the write
        mid-prompt: positions ``[0, start)`` live in radix-SHARED blocks
        another request already wrote, and this write must never touch
        them — neither their values nor, on a quantized pool, their
        scale rows (shared blocks share their scales).

        On a quantized pool (``k_scale``/``v_scale`` present),
        ``scale_map`` (the lease's flat physical block indices, logical
        order) and ``page_block`` drive the quantizing write: the
        prompt's values quantize per (logical block, kv head) symmetric
        amax scale, the scales scatter to the written blocks, and every
        leased block PAST the prompt gets the zero dead sentinel — which
        stops a recycled block's previous-tenant scale from ever
        aliasing into the new request's dequant.

        Example::

            cache = adapter.write_row(cache, lease.slot, row_cache,
                                      len(prompt), pool.kv_len)
        """
        assert start == 0 or page_map is not None, \
            "mid-prompt write start requires the paged path"
        out = dict(cache)
        for key, arr in row_cache.items():
            if key == "pos":
                continue
            row = arr[:, 0]                        # (L, ...) single row
            if key in self.length_keys and page_map is not None:
                assert page_block is not None, "a paged write needs page_block"
                vals = row[:, start:prompt_len]
                if key + "_scale" in out:
                    assert scale_map is not None
                    vals, out = self._quantize_prompt(
                        out, key, vals, start, prompt_len, kv_len,
                        scale_map, int(page_block))
                if prompt_len > start:
                    out[key] = _write_pages(out[key], page_map, vals,
                                            kv_len, int(page_block))
                continue
            if key in self.length_keys:
                pad = kv_len - row.shape[1]
                assert pad >= 0, "prompt bucket outgrew the pool row"
                widths = ((0, 0), (0, pad)) + ((0, 0),) * (row.ndim - 2)
                row = jnp.pad(row, widths)
            out[key] = jax.lax.dynamic_update_slice_in_dim(
                out[key], row[:, None].astype(out[key].dtype), slot, axis=1)
        out["pos"] = out["pos"].at[slot].set(prompt_len)
        return out

    def _quantize_prompt(self, out: dict, key: str, vals, start: int,
                         prompt_len: int, kv_len: int, scale_map, bs: int):
        """Quantize one prompt's ``(L, prompt_len - start, G, hd)``
        values to int8 codes with per-(logical block, kv head) amax
        scales, and land the scales on the lease's physical blocks
        (written blocks get their amax scale, leased blocks past the
        prompt get the zero dead sentinel, and the radix-shared blocks
        BEFORE ``start`` are never touched — a shared block's scale row
        belongs to the block, not the lease).  Returns (codes, updated
        cache dict)."""
        assert start % bs == 0, "write start must be block-aligned"
        n, g = vals.shape[0], vals.shape[2]
        sb0 = start // bs
        npb = -(-prompt_len // bs)
        nw = npb - sb0                            # blocks being written
        skey = key + "_scale"
        b = out[skey].shape[1]
        nb = kv_len // bs
        sflat = out[skey].reshape(n, b * nb, g)
        sm = jnp.asarray(scale_map, jnp.int32)
        if nw > 0:
            pad = npb * bs - prompt_len
            v = jnp.pad(vals.astype(jnp.float32),
                        ((0, 0), (0, pad), (0, 0), (0, 0)))
            v = v.reshape(n, nw, bs, g, -1)
            sc = jnp.max(jnp.abs(v), axis=(2, 4)) / 127.0     # (L, nw, G)
            safe = jnp.where(sc > 0, sc, 1.0)
            codes = jnp.clip(jnp.round(v / safe[:, :, None, :, None]),
                             -127, 127)
            codes = codes.reshape(n, nw * bs, g, -1)[:, :prompt_len - start]
            codes = codes.astype(out[key].dtype)
            sflat = sflat.at[:, sm[sb0:npb]].set(sc)
        else:
            codes = vals.astype(out[key].dtype)
        if len(scale_map) > npb:                 # zero the lease's tail
            sflat = sflat.at[:, sm[npb:]].set(0.0)
        out[skey] = sflat.reshape(out[skey].shape)
        return codes, out

    def grow(self, cache: dict, new_len: int) -> dict:
        """Pad the length-bearing arrays up to the new bucket.  A cache
        with no time axis returns unchanged — the bucket step is then
        purely a KV-block accounting event.  Quantized pools pad their
        scale arrays' block axis with ZEROS (the dead-block sentinel):
        the new physical blocks carry no scale until leased and
        written, exactly like recycled ones."""
        out = dict(cache)
        for key in self.length_keys:
            t_old = out[key].shape[2]
            pad = new_len - t_old
            assert pad > 0, "grow called without a longer bucket"
            widths = ((0, 0), (0, 0), (0, pad)) + \
                ((0, 0),) * (out[key].ndim - 3)
            out[key] = jnp.pad(out[key], widths)
            skey = key + "_scale"
            if skey in out:
                bs = t_old // out[skey].shape[2]     # layout block size
                pad_nb = new_len // bs - out[skey].shape[2]
                out[skey] = jnp.pad(out[skey],
                                    ((0, 0), (0, 0), (0, pad_nb), (0, 0)))
        return out


def _write_pages(arr, page_map, vals, kv_len: int, bs: int):
    """``arr`` (L, B, T, ...) with ``vals`` (L, n, ...) at the flat
    (B*T) positions ``page_map`` (n,), which start on a block boundary.
    A block's tokens are contiguous in one row (``flat_position``), so
    each block is one ``dynamic_update_slice`` at its first token."""
    full, rest = divmod(vals.shape[1], bs)
    first = page_map[::bs]
    rows, offs = first // kv_len, first % kv_len
    tail = (0,) * (arr.ndim - 3)

    def put(a, i, size):
        blk = jax.lax.dynamic_slice_in_dim(vals, i * bs, size, axis=1)
        return jax.lax.dynamic_update_slice(
            a, blk[:, None].astype(a.dtype), (0, rows[i], offs[i]) + tail)

    if full:
        arr = jax.lax.fori_loop(0, full, lambda i, a: put(a, i, bs), arr)
    return put(arr, full, rest) if rest else arr


def _encdec_frames(model, rows: int) -> dict:
    """Stub encoder frames (the conv/mel frontend is a stub repo-wide:
    see ``models.encdec``); shaped per request row."""
    cfg = model.cfg
    return {"frames": jnp.zeros((rows, cfg.encoder_tokens, cfg.d_model),
                                model.dtype)}


def _vlm_patches(model, rows: int) -> dict:
    """Stub image patch embeddings (the vision tower is a stub repo-wide,
    mirroring ``_encdec_frames``); shaped per request row."""
    cfg = model.cfg
    return {"patches": jnp.zeros((rows, cfg.prefix_tokens, cfg.d_model),
                                 model.dtype)}


#: family -> adapter: the single registry the engine consults instead of
#: a family capability check.  All six families are served; ``vlm``
#: rides the dense cache layout with a ``position_offset`` of
#: ``cfg.prefix_tokens`` image-patch positions, which the scheduler,
#: page maps, and growth math all add.  ``shareable_prefix`` marks the
#: families whose paged k/v blocks are a PURE FUNCTION of the prefix
#: tokens (radix prefix sharing): dense only.  moe is out — expert
#: CAPACITY routing couples every token's hidden state (hence its
#: deeper-layer k/v) to the other tokens in its routing group, so a
#: cached prefix block carries its original chunk-mates' fingerprint
#: and aliasing it is not byte-identical to recomputing
#: (``tests/test_prefix_cache.py`` pins this exclusion).  hybrid
#: carries a recurrent state outside the blocks, ssm has no blocks at
#: all, encdec/vlm prepend non-token context — none of them can alias
#: a prompt prefix.
ADAPTERS: dict[str, CacheAdapter] = {
    "dense": FamilyCacheAdapter("dense", shareable_prefix=True),
    "moe": FamilyCacheAdapter("moe"),
    "ssm": FamilyCacheAdapter("ssm", length_keys=(), prefill_buckets=False),
    "hybrid": FamilyCacheAdapter("hybrid"),
    "encdec": FamilyCacheAdapter("encdec", extras=_encdec_frames),
    "vlm": FamilyCacheAdapter("vlm", extras=_vlm_patches,
                              prefix_offset=lambda m: m.cfg.prefix_tokens),
}


def get_adapter(family: str) -> CacheAdapter:
    """The registered ``CacheAdapter`` for a model family; raises
    ``NotImplementedError`` with the served set for absent families.

    Example::

        >>> get_adapter("dense").family
        'dense'
    """
    try:
        return ADAPTERS[family]
    except KeyError:
        raise NotImplementedError(
            f"no CacheAdapter for family {family!r}; the ragged pool "
            f"serves {tuple(sorted(ADAPTERS))}") from None
