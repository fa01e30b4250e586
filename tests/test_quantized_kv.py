"""Quantized (int8) paged KV pool + dequant-fused decode (PR 9).

Four pin families:

  * kernel parity — the dequant-fused sweep (blocked reference AND the
    scalar-prefetch Pallas kernel under interpret) matches the
    dequantize-then-dense oracle, and ``paged_dequant_gather`` (the
    ablation read) round-trips the per-(block, head) symmetric codes;
  * pool lifecycle — prompt quantization resets every leased block's
    scale (recycled blocks can never alias a previous tenant's scale),
    decode writes through retired/unmapped table entries drop without
    touching codes OR scales, and pool growth pads the scale grid
    without moving live scales;
  * accuracy — an int8 engine tracks its fp32 twin within a bounded
    per-tick logit error for ALL FIVE families, through mid-decode slot
    recycling and pool growth (the attention-free ssm family is exactly
    bit-equal: it has no KV to quantize);
  * tuning — ``kv_dtype`` is a signature dimension: fp32 and int8
    routers resolve DIFFERENT fused blocks on a vmem-constrained part,
    and the int8 engine executes the int8 plan (spy), while the fp32
    default keeps today's cache layout and an int8-free lowering.
"""

import dataclasses

import numpy as np
import pytest

from repro.configs.base import ShapeConfig, get_config
from repro.serve import ServeEngine, get_adapter
from repro.tuner import TuningCache

FAMILIES = ["smollm-135m", "deepseek-moe-16b", "mamba2-1.3b",
            "zamba2-7b", "whisper-medium"]

#: 5 ragged requests through 2 slots (mid-decode recycling), including
#: one long prompt that forces a pool-length bucket step (growth) —
#: the same mix tests/test_paged_decode.py drives
_PROMPTS = [[7, 3, 99], [11, 5, 2, 42, 17, 101, 9],
            list(range(2, 38)), [250, 1], [33, 44, 55, 66]]
_MAX_NEW = 3


@pytest.fixture(scope="module")
def f32_cfg():
    return dataclasses.replace(get_config("smollm-135m").reduced(),
                               dtype="float32")


def _quantize_blocks(x, bs):
    """Per-(block, head) symmetric int8 codes + scales for a (b, t, g, d)
    cache laid out in ``bs``-token blocks (the pool's storage scheme)."""
    b, t, g, d = x.shape
    nb = t // bs
    v = x.reshape(b, nb, bs, g, d)
    sc = np.max(np.abs(v), axis=(2, 4)) / 127.0          # (b, nb, g)
    safe = np.where(sc > 0, sc, 1.0)
    codes = np.clip(np.round(v / safe[:, :, None, :, None]), -127, 127)
    return codes.reshape(b, t, g, d).astype(np.int8), sc.astype(np.float32)


def _paged_case(seed, b=3, t=64, g=2, d=8, bs=16):
    rng = np.random.default_rng(seed)
    nb = t // bs
    clen = rng.integers(1, t + 1, size=b)
    perm = list(rng.permutation(b * nb))
    tables = np.full((b, nb), -1, np.int64)
    for i in range(b):
        for j in range(-(-int(clen[i]) // bs)):
            tables[i, j] = perm.pop()
    k = rng.standard_normal((b, t, g, d)).astype(np.float32)
    v = rng.standard_normal((b, t, g, d)).astype(np.float32)
    q = rng.standard_normal((b, g, 1, d)).astype(np.float32)
    return q, k, v, tables, clen


# --------------------------------------------------------------------------- #
# Kernel parity: fused dequant == dequantize-then-dense oracle
# --------------------------------------------------------------------------- #


def test_fused_int8_matches_dequant_oracle():
    """The dequant-fused sweep (reference AND Pallas-interpret) on int8
    codes + scales reproduces the dense sweep over the materialized
    dequantized cache — fusion changes the schedule, not the math."""
    import jax.numpy as jnp

    from repro.kernels.paged_decode_attention import (
        paged_decode_attention_pallas, paged_decode_attention_ref)
    from repro.kernels.paged_gather import paged_dequant_gather_ref
    from repro.models.attention import decode_attention_grouped

    bs = 16
    q, k, v, tables, clen = _paged_case(0, bs=bs)
    kc, ks = _quantize_blocks(k, bs)
    vc, vs = _quantize_blocks(v, bs)
    kj, vj = jnp.asarray(kc), jnp.asarray(vc)
    ksj, vsj = jnp.asarray(ks), jnp.asarray(vs)
    tj, cj = jnp.asarray(tables), jnp.asarray(clen)
    kl = paged_dequant_gather_ref(kj, ksj, tj, bs)
    vl = paged_dequant_gather_ref(vj, vsj, tj, bs)
    expected = np.asarray(decode_attention_grouped(jnp.asarray(q),
                                                   kl, vl, cj))
    for block_s in (16, 32, 64):
        got = np.asarray(paged_decode_attention_ref(
            jnp.asarray(q), kj, vj, tj, cj, page_block=bs, block_s=block_s,
            k_scale=ksj, v_scale=vsj))
        np.testing.assert_allclose(got, expected, rtol=1e-5, atol=1e-5,
                                   err_msg=f"ref block_s={block_s}")
        got_p = np.asarray(paged_decode_attention_pallas(
            jnp.asarray(q), kj, vj, tj, cj, page_block=bs,
            block_s=block_s, k_scale=ksj, v_scale=vsj, interpret=True))
        np.testing.assert_allclose(got_p, expected, rtol=1e-5, atol=1e-5,
                                   err_msg=f"pallas block_s={block_s}")


def test_dequant_gather_roundtrips_codes():
    """``paged_dequant_gather`` (ref and Pallas) recovers the original
    values to within one quantization step — and ref == Pallas exactly."""
    import jax.numpy as jnp

    from repro.kernels.paged_gather import (paged_dequant_gather_pallas,
                                            paged_dequant_gather_ref,
                                            paged_gather_ref)

    bs = 16
    _, k, _, tables, clen = _paged_case(3, bs=bs)
    kc, ks = _quantize_blocks(k, bs)
    kj, ksj = jnp.asarray(kc), jnp.asarray(ks)
    tj = jnp.asarray(tables)
    ref = np.asarray(paged_dequant_gather_ref(kj, ksj, tj, bs))
    pal = np.asarray(paged_dequant_gather_pallas(kj, ksj, tj, bs,
                                                 interpret=True))
    np.testing.assert_array_equal(ref, pal)
    # gathered logical rows within the lease match the source to one step
    orig = np.asarray(paged_gather_ref(jnp.asarray(k), tj, bs))
    step = ks.max() + 1e-9
    for i, n in enumerate(clen):
        np.testing.assert_allclose(ref[i, :n], orig[i, :n], atol=step)


# --------------------------------------------------------------------------- #
# Pool lifecycle: scale hygiene under recycling / growth / retirement
# --------------------------------------------------------------------------- #


def test_recycled_blocks_never_alias_scales(f32_cfg):
    """Re-leasing blocks to a new tenant resets their scales from the
    new prompt alone: the previous tenant's (larger) scales must not
    survive, and the tail blocks of the new lease must come back zeroed
    (the fresh-block sentinel the decode write keys on)."""
    import jax
    import jax.numpy as jnp

    from repro.models import build_model

    adapter = get_adapter("dense")
    model = build_model(f32_cfg)
    slots, kv_len, bs = 2, 64, 16
    nb = kv_len // bs
    cache = adapter.init_pool(model, slots, kv_len, kv_dtype="int8",
                              block_size=bs)
    assert "k_scale" in cache and "v_scale" in cache
    assert cache["k"].dtype == jnp.int8

    g = cache["k"].shape[3]
    rng = np.random.default_rng(0)

    def row_cache(n, amp):
        return {"k": jnp.asarray(amp * rng.standard_normal(
                    (cache["k"].shape[0], 1, n, g, cache["k"].shape[4])),
                    jnp.float32),
                "v": jnp.asarray(amp * rng.standard_normal(
                    (cache["k"].shape[0], 1, n, g, cache["k"].shape[4])),
                    jnp.float32),
                "pos": jnp.asarray(n, jnp.int32)}

    def maps(blocks, n):
        pid = np.asarray(blocks)
        tok = np.arange(n)
        p = pid[tok // bs]
        pm = jnp.asarray((p % slots) * kv_len + (p // slots) * bs + tok % bs,
                         jnp.int32)
        sm = ((pid % slots) * nb + pid // slots).astype(np.int32)
        return pm, sm

    blocks = [0, 2, 4, 6]                      # one slot-0 lease, 4 blocks
    # tenant A: LOUD prompt filling 3 blocks
    pm, sm = maps(blocks, 40)
    cache = adapter.write_row(cache, 0, row_cache(40, amp=100.0), 40,
                              kv_len, page_map=pm, scale_map=sm,
                              page_block=bs)
    loud = np.asarray(cache["k_scale"]).reshape(-1, slots * nb, g)
    assert loud[:, sm[:3]].max() > 0.1
    # tenant B on the SAME blocks: quiet prompt filling 1 block
    pm, sm = maps(blocks, 12)
    cache = adapter.write_row(cache, 0, row_cache(12, amp=0.01), 12,
                              kv_len, page_map=pm, scale_map=sm,
                              page_block=bs)
    sc = np.asarray(cache["k_scale"]).reshape(-1, slots * nb, g)
    assert sc[:, sm[0]].max() <= 1e-3, \
        "tenant A's scale leaked into tenant B's block"
    assert not sc[:, sm[1:]].any(), \
        "recycled tail blocks kept a previous tenant's scales"


def test_int8_decode_write_drops_on_retired_rows():
    """``_paged_quant_write``: rows whose table entry is unmapped (-1)
    or whose position overruns the table write NOTHING — codes and
    scales both stay put — while mapped rows requantize exactly their
    own block."""
    import jax.numpy as jnp

    from repro.models.attention import _paged_quant_write

    rng = np.random.default_rng(7)
    b, t, g, d, bs = 3, 32, 2, 4, 8
    nb = t // bs
    codes = rng.integers(-127, 128, size=(b, t, g, d)).astype(np.int8)
    scale = (rng.random((b, nb, g)) + 0.1).astype(np.float32)
    tables = np.array([[-1, -1, -1, -1],       # retired row
                       [3, 1, -1, -1],
                       [0, 4, 2, 5]], np.int64)
    pos = np.array([5, 40, 9])                 # row 1 overruns t=32
    new = rng.standard_normal((b, g, d)).astype(np.float32)
    out_c, out_s = _paged_quant_write(
        jnp.asarray(codes), jnp.asarray(scale), jnp.asarray(new),
        jnp.asarray(pos), page_tables=jnp.asarray(tables), page_block=bs)
    out_c, out_s = np.asarray(out_c), np.asarray(out_s)
    # only row 2's write lands: pid=4 -> physical (row 1, block 1)
    pid = tables[2, pos[2] // bs]
    prow, poff = pid % b, pid // b
    touched_c = np.zeros((b, t), bool)
    touched_c[prow, poff * bs:(poff + 1) * bs] = True
    touched_s = np.zeros((b, nb), bool)
    touched_s[prow, poff] = True
    np.testing.assert_array_equal(out_c[~touched_c], codes[~touched_c])
    np.testing.assert_array_equal(out_s[~touched_s], scale[~touched_s])
    # the landed token dequantizes back to within one step
    got = (out_c[prow, poff * bs + pos[2] % bs].astype(np.float32)
           * out_s[prow, poff][:, None])
    np.testing.assert_allclose(got, new[2], atol=float(out_s.max()) + 1e-9)


def test_decode_write_into_fresh_block_wipes_stale_codes():
    """A decode write into a zero-scale (fresh or recycled) block wipes
    whatever codes the block held: the block must contain ONLY the new
    token afterwards — never a previous tenant's data dequantized at
    the new scale."""
    import jax.numpy as jnp

    from repro.models.attention import _paged_quant_write

    b, t, g, d, bs = 2, 32, 2, 4, 8
    nb = t // bs
    codes = np.full((b, t, g, d), 55, np.int8)     # stale garbage
    scale = np.zeros((b, nb, g), np.float32)       # fresh-block sentinel
    tables = np.array([[2, -1, -1, -1], [1, -1, -1, -1]], np.int64)
    pos = np.array([3, 2])
    new = np.ones((b, g, d), np.float32)
    out_c, out_s = _paged_quant_write(
        jnp.asarray(codes), jnp.asarray(scale), jnp.asarray(new),
        jnp.asarray(pos), page_tables=jnp.asarray(tables), page_block=bs)
    out_c, out_s = np.asarray(out_c), np.asarray(out_s)
    for i in range(b):
        pid = tables[i, 0]
        prow, poff = pid % b, pid // b
        blk = out_c[prow, poff * bs:(poff + 1) * bs]
        hot = pos[i] % bs
        np.testing.assert_array_equal(blk[hot], 127)   # the token
        mask = np.arange(bs) != hot
        assert not blk[mask].any(), "stale codes survived the wipe"
        np.testing.assert_allclose(out_s[prow, poff], 1.0 / 127.0,
                                   rtol=1e-6)


def test_grow_pads_scale_grid_in_place(f32_cfg):
    """Pool growth pads the scale grid's block axis with zeros and keeps
    every live (slot, block-offset) scale where it was — the physical
    identity the fused kernels resolve is growth-stable."""
    import jax

    from repro.models import build_model

    adapter = get_adapter("dense")
    model = build_model(f32_cfg)
    cache = adapter.init_pool(build_model(f32_cfg), 2, 32, kv_dtype="int8",
                              block_size=16)
    key = jax.random.key(1)
    sc = jax.random.uniform(key, cache["k_scale"].shape)
    cache["k_scale"] = sc
    grown = adapter.grow(dict(cache), 64)
    assert grown["k"].shape[2] == 64
    assert grown["k_scale"].shape[2] == 4
    np.testing.assert_array_equal(np.asarray(grown["k_scale"])[:, :, :2],
                                  np.asarray(sc))
    assert not np.asarray(grown["k_scale"])[:, :, 2:].any()


# --------------------------------------------------------------------------- #
# Accuracy: int8 engine vs fp32 twin, all five families
# --------------------------------------------------------------------------- #


def _drive_with_logits(cfg, params, kv_dtype):
    eng = ServeEngine(cfg, slots=2, max_len=64, params=params,
                      tuning_cache=TuningCache(path=None),
                      kv_dtype=kv_dtype)
    log = []
    real = eng._decode

    def spy(*a, **kw):
        out = real(*a, **kw)       # moe steps also return the expert load
        log.append(np.asarray(out[0]))
        return out

    eng._decode = spy
    reqs = [eng.submit(p, max_new_tokens=_MAX_NEW) for p in _PROMPTS]
    report = eng.run()
    assert report.summary.n_completed == len(_PROMPTS)
    assert report.pool_growths >= 1, "mix never grew the pool"
    return eng, report, reqs, log


@pytest.mark.parametrize("arch", FAMILIES)
def test_int8_logit_error_bounded_all_families(arch):
    """Through slot recycling AND pool growth, every decode tick's
    logits under the int8 pool stay within a small bound of the fp32
    pool's — and the argmax token streams agree on this mix.  The
    attention-free ssm family must be exactly equal (nothing was
    quantized)."""
    import jax

    from repro.models import build_model

    cfg = dataclasses.replace(get_config(arch).reduced(), dtype="float32")
    params = build_model(cfg).init(jax.random.key(0))
    e32, r32, q32, l32 = _drive_with_logits(cfg, params, "fp32")
    e8, r8, q8, l8 = _drive_with_logits(cfg, params, "int8")
    assert "k_scale" not in e32._cache
    if not cfg.is_attention_free:
        assert "k_scale" in e8._cache and e8._cache["k"].dtype == np.int8
    assert len(l32) == len(l8), "tick schedules diverged"
    err = max(float(np.max(np.abs(a - b))) for a, b in zip(l32, l8))
    scale = max(float(np.max(np.abs(a))) for a in l32)
    if cfg.is_attention_free:
        assert err == 0.0, "ssm has no KV cache; int8 must be a no-op"
    else:
        assert err <= 0.05 * scale, \
            f"{arch}: int8 logit error {err:.4f} vs fp32 scale {scale:.2f}"
    for a, b in zip(q32, q8):
        assert r32.outputs[a.rid] == r8.outputs[b.rid], \
            f"{arch}: int8 changed the argmax token stream on this mix"


def test_int8_cache_bytes_quartered(f32_cfg):
    """The point of the exercise: the int8 pool's KV bytes (codes +
    scales) are under ~30% of the fp32 pool's for the same geometry."""
    import jax

    from repro.models import build_model

    params = build_model(f32_cfg).init(jax.random.key(0))

    def kv_bytes(kvd):
        eng = ServeEngine(f32_cfg, slots=2, max_len=64, params=params,
                          tuning_cache=TuningCache(path=None), kv_dtype=kvd)
        return sum(np.asarray(v).nbytes for k, v in eng._cache.items()
                   if k.startswith(("k", "v")))

    b32, b8 = kv_bytes("fp32"), kv_bytes("int8")
    assert b8 < 0.30 * b32, (b8, b32)


def test_int8_requires_paged_pool(f32_cfg):
    with pytest.raises(ValueError, match="paged"):
        ServeEngine(f32_cfg, slots=2, max_len=64, paged=False,
                    kv_dtype="int8", tuning_cache=TuningCache(path=None))
    with pytest.raises(ValueError):
        ServeEngine(f32_cfg, slots=2, max_len=64, kv_dtype="fp16",
                    tuning_cache=TuningCache(path=None))


# --------------------------------------------------------------------------- #
# Tuning: kv_dtype is a signature dimension
# --------------------------------------------------------------------------- #


def _vmem_constrained_hw():
    from repro.core.hw import TPU_REGISTRY
    return dataclasses.replace(TPU_REGISTRY["cpu_sim"],
                               vmem_budget_bytes=262144)


def test_tuner_resolves_different_block_per_kv_dtype(f32_cfg):
    """On a vmem-constrained part the int8 pool's 4x byte headroom must
    reach the planner: fp32 and int8 routers resolve DIFFERENT fused
    blocks for the same bucket, under distinct signatures."""
    from repro.serve.buckets import BucketRouter, BucketSpec

    hw = _vmem_constrained_hw()
    spec = BucketSpec(max_len=256, min_len=32)

    def plan(kvd):
        r = BucketRouter(f32_cfg, spec, slots=2, hw=hw,
                         cache=TuningCache(path=None), page_block=16,
                         kv_dtype=kvd)
        return r.resolve(r.bucket(256))

    p32, p8 = plan("fp32"), plan("int8")
    assert p32.sig.key != p8.sig.key, "kv_dtype missing from signature"
    assert p32.paged_decode_block != p8.paged_decode_block, \
        "int8 byte width never reached the fused-block planner"


def test_int8_engine_executes_int8_plan(f32_cfg, monkeypatch):
    """The int8 engine must RUN the int8-resolved fused block (spy on
    the executed kernel), not the fp32 plan for the same bucket."""
    import jax

    from repro.kernels import paged_decode_attention as pda_mod
    from repro.models import build_model

    seen = []
    real = pda_mod.paged_decode_attention

    def spy(q, kc, vc, tables, clen, **kw):
        seen.append((int(kw["block_s"]), kw.get("k_scale") is not None))
        return real(q, kc, vc, tables, clen, **kw)

    monkeypatch.setattr(pda_mod, "paged_decode_attention", spy)
    hw = _vmem_constrained_hw()
    params = build_model(f32_cfg).init(jax.random.key(0))
    eng = ServeEngine(f32_cfg, slots=2, max_len=256, params=params, hw=hw,
                      tuning_cache=TuningCache(path=None), kv_dtype="int8")
    eng.submit(list(range(2, 200)), max_new_tokens=2)
    report = eng.run()
    assert report.summary.n_completed == 1
    plan = eng.router.resolve(eng.router.bucket(256))
    assert (plan.paged_decode_block, True) in seen, \
        "executed fused block is not the int8 plan"
    # and the fp32 router's choice for the same bucket differs here
    from repro.serve.buckets import BucketRouter
    r32 = BucketRouter(f32_cfg, eng.spec, slots=2, hw=hw,
                       cache=TuningCache(path=None), page_block=16)
    assert r32.resolve(r32.bucket(256)).paged_decode_block \
        != plan.paged_decode_block


def test_fp32_default_keeps_cache_layout_and_lowering(f32_cfg):
    """``kv_dtype`` unset == ``kv_dtype="fp32"``: same cache pytree (no
    scale keys, fp32 storage) and byte-identical decode lowering — the
    quantized path costs nothing unless asked for."""
    import jax.numpy as jnp

    def lower(**kw):
        eng = ServeEngine(f32_cfg, slots=2, max_len=32,
                          tuning_cache=TuningCache(path=None), **kw)
        tables = jnp.asarray(eng._tables)
        return eng, eng._decode.lower(
            eng.params, dict(eng._cache), jnp.asarray(eng._tokens),
            decode_block=128, page_tables=tables,
            page_block=eng._block_size, paged_decode_block=16).as_text()

    e_def, hlo_def = lower()
    e_f32, hlo_f32 = lower(kv_dtype="fp32")
    assert sorted(e_def._cache) == sorted(e_f32._cache)
    assert not any(k.endswith("_scale") for k in e_def._cache)
    assert e_def._cache["k"].dtype == jnp.float32
    assert hlo_def == hlo_f32
    assert "s8[" not in hlo_def and "xi8>" not in hlo_def, \
        "int8 leaked into the fp32 lowering"
