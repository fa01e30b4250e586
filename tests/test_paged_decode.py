"""Fused table-consuming paged flash decode (PR 6 acceptance).

Three pin families:

  * parity — the fused sweep (blocked reference AND scalar-prefetch
    Pallas kernel under interpret) matches gather-then-dense-decode at
    the kernel level, and the fused engine default is token-exact
    against both the gather ablation and the sequential scalar-pos path
    for ALL FIVE families, through slot recycling and pool growth;
  * block-table invariants (hypothesis when installed, seeded sweep
    otherwise) — random admit/retire/grow keeps live tables pairwise
    disjoint, the column-major ``pid -> (pid % slots, (pid//slots)*bs)``
    grid mapping round-trips, and scatter writes through retired
    (unmapped) table entries drop without touching any other location;
  * executed-plan pins — the router-resolved ``block_s`` + table
    geometry reach the kernel call the engine actually RUNS (spy),
    changing the plan changes the lowered step while the logits stay
    fixed, and the unpaged step lowers byte-identical to the pre-PR
    decode path.
"""

import dataclasses
import random

import numpy as np
import pytest

from repro.configs.base import ShapeConfig, get_config
from repro.serve import KVCachePool, ServeEngine
from repro.tuner import TuningCache

try:
    import hypothesis.strategies as st
    from hypothesis import given, settings
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

#: one representative arch per CacheAdapter family
FAMILIES = ["smollm-135m", "deepseek-moe-16b", "mamba2-1.3b",
            "zamba2-7b", "whisper-medium"]


@pytest.fixture(scope="module")
def f32_cfg():
    return dataclasses.replace(get_config("smollm-135m").reduced(),
                               dtype="float32")


def _paged_case(seed, b=3, t=64, g=2, d=8, bs=16):
    """A random paged-decode workload: disjoint per-row leases (ragged
    lengths, permuted physical blocks, unmapped -1 tails) over a random
    physical cache."""
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
# Kernel-level parity: fused == gather + dense sweep
# --------------------------------------------------------------------------- #


def test_fused_matches_gather_plus_dense_sweep():
    """Across tuned ``block_s`` values, the fused sweep (reference AND
    Pallas-interpret kernel) reproduces gather-then-dense-decode on
    ragged leases with unmapped table tails — the zero-materialization
    read is the same math."""
    import jax.numpy as jnp

    from repro.kernels.paged_decode_attention import (
        paged_decode_attention_pallas, paged_decode_attention_ref)
    from repro.kernels.paged_gather import paged_gather_ref
    from repro.models.attention import decode_attention_grouped

    bs = 16
    q, k, v, tables, clen = _paged_case(0, bs=bs)
    kj, vj = jnp.asarray(k), jnp.asarray(v)
    tj, cj = jnp.asarray(tables), jnp.asarray(clen)
    kl = paged_gather_ref(kj, tj, bs)
    vl = paged_gather_ref(vj, tj, bs)
    expected = np.asarray(decode_attention_grouped(jnp.asarray(q),
                                                   kl, vl, cj))
    for block_s in (16, 32, 48, 64, 128):
        got = np.asarray(paged_decode_attention_ref(
            jnp.asarray(q), kj, vj, tj, cj, page_block=bs, block_s=block_s))
        np.testing.assert_allclose(got, expected, rtol=1e-5, atol=1e-5,
                                   err_msg=f"ref block_s={block_s}")
        if block_s % bs == 0:
            got_p = np.asarray(paged_decode_attention_pallas(
                jnp.asarray(q), kj, vj, tj, cj, page_block=bs,
                block_s=block_s, interpret=True))
            np.testing.assert_allclose(got_p, expected, rtol=1e-5,
                                       atol=1e-5,
                                       err_msg=f"pallas block_s={block_s}")


def test_fused_ref_honours_sliding_window():
    """The blocked fused reference carries the traced sliding-window
    mask the Pallas path declines — same masking as the dense sweep."""
    import jax.numpy as jnp

    from repro.kernels.paged_decode_attention import \
        paged_decode_attention_ref
    from repro.kernels.paged_gather import paged_gather_ref
    from repro.models.attention import decode_attention_grouped

    bs = 16
    q, k, v, tables, clen = _paged_case(1, bs=bs)
    kj, vj = jnp.asarray(k), jnp.asarray(v)
    tj, cj = jnp.asarray(tables), jnp.asarray(clen)
    kl = paged_gather_ref(kj, tj, bs)
    vl = paged_gather_ref(vj, tj, bs)
    for window in (4, 9):
        expected = np.asarray(decode_attention_grouped(
            jnp.asarray(q), kl, vl, cj, window=window))
        got = np.asarray(paged_decode_attention_ref(
            jnp.asarray(q), kj, vj, tj, cj, page_block=bs, block_s=32,
            window=window))
        np.testing.assert_allclose(got, expected, rtol=1e-5, atol=1e-5)


def test_pallas_request_on_illegal_geometry_raises():
    """A requested kernel on a cache or ``block_s`` that is not whole
    pages, or on pages that are not whole rows of lanes, raises instead
    of running the reference in its place, while a sliding window still
    takes the documented reference path."""
    import jax.numpy as jnp

    from repro.kernels.paged_decode_attention import (
        paged_decode_attention, paged_decode_attention_ref)

    bs = 16
    q, k, v, tables, clen = _paged_case(2, bs=bs)
    qj, kj, vj = jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)
    tj, cj = jnp.asarray(tables), jnp.asarray(clen)
    for kc, vc, block_s in ((kj[:, :40], vj[:, :40], 32),   # ragged cache
                            (kj, vj, 24),                    # ragged block
                            (kj, vj, 8)):                    # sub-page block
        with pytest.raises(ValueError, match="whole pages"):
            paged_decode_attention(qj, kc, vc, tj, cj, page_block=bs,
                                   block_s=block_s, use_pallas=True,
                                   interpret=True)
    with pytest.raises(ValueError, match="lanes"):     # 4x2x8 < 128 lanes
        paged_decode_attention(qj, kj, vj, tj, cj, page_block=4,
                               block_s=16, use_pallas=True, interpret=True)
    got = paged_decode_attention(qj, kj, vj, tj, cj, page_block=bs,
                                 block_s=32, window=5, use_pallas=True,
                                 interpret=True)
    ref = paged_decode_attention_ref(qj, kj, vj, tj, cj, page_block=bs,
                                     block_s=32, window=5)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


# --------------------------------------------------------------------------- #
# The fused kernel's live-page sweep: ragged rows, retired rows, dead pages
# --------------------------------------------------------------------------- #

#: a retired slot: its table is all -1 while its position keeps advancing
RETIRED = None


def _live_page_case(lens, kv, *, t=1024, bs=16, g=3, r=3, d=64, seed=0):
    """Rows of the given cache lengths (``RETIRED`` for a retired slot)
    over a ``t``-position physical pool at smollm-135m's head geometry.
    Live rows lease permuted physical pages, never page 0; the pool holds
    bf16 values or int8 codes with per-(page, head) f32 scales.  Returns
    the kernel's arguments and the set of mapped physical pages."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    b, nb = len(lens), t // bs
    free = list(rng.permutation(np.arange(1, b * nb)))
    tables = np.full((b, nb), -1, np.int32)
    clen = np.empty(b, np.int32)
    for i, n in enumerate(lens):
        clen[i] = t + 37 if n is RETIRED else n
        if n is not RETIRED:
            for j in range(-(-n // bs)):
                tables[i, j] = free.pop()
    k = rng.standard_normal((b, t, g, d)).astype(np.float32)
    v = rng.standard_normal((b, t, g, d)).astype(np.float32)
    q = rng.standard_normal((b, g, r, d)).astype(np.float32)
    args = dict(q=q, tables=tables, clen=clen)
    if kv == "bf16":
        args.update(k=jnp.asarray(k, jnp.bfloat16),
                    v=jnp.asarray(v, jnp.bfloat16), ks=None, vs=None)
    else:
        for name, x in (("k", k), ("v", v)):
            blk = x.reshape(b, nb, bs, g, d)
            sc = np.abs(blk).max(axis=(2, 4)) / 127.0            # (b, nb, g)
            codes = np.round(blk / sc[:, :, None, :, None])
            args[name] = codes.reshape(b, t, g, d).astype(np.int8)
            args[name + "s"] = sc.astype(np.float32)
    return args, set(tables[tables >= 0].tolist())


def _run_fused(args, *, bs, block_s, pallas):
    import jax.numpy as jnp

    from repro.kernels.paged_decode_attention import (
        paged_decode_attention_pallas, paged_decode_attention_ref)

    kw = dict(page_block=bs, block_s=block_s,
              k_scale=None if args["ks"] is None else jnp.asarray(args["ks"]),
              v_scale=None if args["vs"] is None else jnp.asarray(args["vs"]))
    if pallas:
        fn, kw = paged_decode_attention_pallas, dict(kw, interpret=True)
    else:
        fn = paged_decode_attention_ref
    return np.asarray(fn(jnp.asarray(args["q"]), jnp.asarray(args["k"]),
                         jnp.asarray(args["v"]), jnp.asarray(args["tables"]),
                         jnp.asarray(args["clen"]), **kw))


@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("block_s", [64, 1024], ids=["chunked", "whole"])
@pytest.mark.parametrize("row_len", [1, 23, 32, "block_edge", 1024, RETIRED],
                         ids=["one", "mid_page", "page_edge", "block_edge",
                              "full", "retired"])
def test_fused_kernel_matches_reference_on_ragged_rows(row_len, block_s, kv):
    """The Pallas kernel (interpret mode) against the blocked reference,
    with one row of each cache length a pool sees beside a row that
    spans two fetched sub-blocks, a retired row and a short row.  Live
    rows match the reference; a retired row writes exact zeros."""
    bs = 16
    n = block_s if row_len == "block_edge" else row_len
    lens = [n, 600, RETIRED, 77]
    args, _ = _live_page_case(lens, kv, bs=bs)
    got = _run_fused(args, bs=bs, block_s=block_s, pallas=True)
    want = _run_fused(args, bs=bs, block_s=block_s, pallas=False)
    assert np.isfinite(got).all()
    for i, m in enumerate(lens):
        if m is RETIRED:
            np.testing.assert_array_equal(got[i], 0.0)
        else:
            np.testing.assert_allclose(got[i], want[i], rtol=1e-5,
                                       atol=1e-5, err_msg=f"row {i} ({m})")


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_fused_kernel_never_reads_dead_pages(kv):
    """Every physical page no live row maps (page 0 included, which a
    -1 entry once aliased) holds NaN: NaN values in a bf16 pool, NaN
    scales in an int8 one.  The kernel's output for live rows equals the
    reference's on the clean pool and is finite, so no dead page is ever
    read into the math, not even to be multiplied by zero."""
    bs, t = 16, 256
    lens = [200, RETIRED, 1, 77, t]
    clean, mapped = _live_page_case(lens, kv, t=t, bs=bs, seed=1)
    b, nb = len(lens), t // bs
    dead = [p for p in range(b * nb) if p not in mapped]
    assert 0 in dead
    poisoned = dict(clean)
    if kv == "bf16":
        for name in ("k", "v"):
            x = np.asarray(clean[name], np.float32).reshape(b, nb, bs, -1)
            for p in dead:             # pid -> (pid % B, pid // B)
                x[p % b, p // b] = np.nan
            poisoned[name] = x.reshape(clean[name].shape).astype(
                clean[name].dtype)
    else:
        for name in ("ks", "vs"):
            sc = clean[name].copy()
            for p in dead:
                sc[p % b, p // b] = np.nan
            poisoned[name] = sc
    got = _run_fused(poisoned, bs=bs, block_s=64, pallas=True)
    want = _run_fused(clean, bs=bs, block_s=64, pallas=False)
    assert np.isfinite(got).all()
    for i, m in enumerate(lens):
        if m is not RETIRED:
            np.testing.assert_allclose(got[i], want[i], rtol=1e-5,
                                       atol=1e-5, err_msg=f"row {i} ({m})")


# --------------------------------------------------------------------------- #
# Engine-level parity: all five families, recycling + growth
# --------------------------------------------------------------------------- #


def _sequential_reference(cfg, params, prompts, max_new):
    import jax
    import jax.numpy as jnp

    from repro.launch.mesh import make_local_mesh
    from repro.launch.steps import make_decode_step, make_prefill_step
    from repro.models import build_model
    from repro.runtime import sharding as shd
    from repro.serve import get_adapter

    model = build_model(cfg)
    extras = get_adapter(cfg.family).prefill_extras(model, 1)
    mesh = make_local_mesh(1, 1)
    outs = []
    for p in prompts:
        max_len = len(p) + max_new + 1
        plan = shd.resolve_plan(cfg, mesh,
                                ShapeConfig("serve", max_len, 1, "decode"))
        prefill = jax.jit(make_prefill_step(model, plan, max_len))
        decode = jax.jit(make_decode_step(model, plan))
        logits, cache = prefill(
            params, {"tokens": jnp.asarray([p], jnp.int32), **extras})
        out = [int(jnp.argmax(logits[0, -1]))]
        for _ in range(max_new - 1):
            logits, cache = decode(params, cache,
                                   jnp.asarray([[out[-1]]], jnp.int32))
            lg = logits[:, 0] if logits.ndim == 3 else logits
            out.append(int(jnp.argmax(lg[0])))
        outs.append(out)
    return outs


#: 5 ragged requests through 2 slots (mid-decode recycling), including
#: one long prompt that forces a pool-length bucket step (growth)
_PROMPTS = [[7, 3, 99], [11, 5, 2, 42, 17, 101, 9],
            list(range(2, 38)), [250, 1], [33, 44, 55, 66]]
_MAX_NEW = 3


@pytest.mark.parametrize("arch", FAMILIES)
def test_fused_engine_token_exact_all_families(arch):
    """The fused default AND the gather ablation are token-exact against
    the one-request-at-a-time scalar-pos path for every CacheAdapter
    family, under mid-decode slot recycling and pool growth.  (For the
    attention-free ssm family the fused plan is ``None`` — the pin is
    that the default flip stays harmless end to end.)"""
    import jax

    from repro.models import build_model

    cfg = dataclasses.replace(get_config(arch).reduced(), dtype="float32")
    params = build_model(cfg).init(jax.random.key(0))
    ref = _sequential_reference(cfg, params, _PROMPTS, _MAX_NEW)

    for fused in (True, False):
        # whole-prompt prefill: the pin is BITWISE token equality with a
        # one-request-at-a-time reference, so the chunked default's
        # float-reordering (argmax flips on random-init weights) is
        # opted out — chunked parity has its own suite
        eng = ServeEngine(cfg, slots=2, max_len=64, params=params,
                          fused_decode=fused, prefill_chunk=None,
                          tuning_cache=TuningCache(path=None))
        reqs = [eng.submit(p, max_new_tokens=_MAX_NEW) for p in _PROMPTS]
        report = eng.run()
        assert report.summary.n_completed == len(_PROMPTS)
        for req, p, expected in zip(reqs, _PROMPTS, ref):
            assert report.outputs[req.rid][len(p):] == expected, \
                f"{arch} fused={fused}: tokens diverged"
        assert report.pool_growths >= 1, "mix never grew the pool"
        if not cfg.is_attention_free:
            plan = eng.router.resolve(eng.router.bucket(eng.pool.kv_len))
            assert plan.paged_decode_block is not None
            assert plan.paged_decode_block % eng._block_size == 0


def test_fused_pallas_path_token_exact(f32_cfg):
    """Under force-interpret (the Pallas decode path on CPU) the fused
    scalar-prefetch kernel and the gather-then-Pallas-sweep ablation
    produce identical tokens on identical traffic."""
    import jax

    from repro.kernels import ops
    from repro.models import build_model

    params = build_model(f32_cfg).init(jax.random.key(0))
    outs = {}
    with ops.force("interpret"):
        for fused in (True, False):
            eng = ServeEngine(f32_cfg, slots=2, max_len=64, params=params,
                              fused_decode=fused,
                              tuning_cache=TuningCache(path=None))
            reqs = [eng.submit(p, max_new_tokens=_MAX_NEW)
                    for p in _PROMPTS[:3]]
            report = eng.run()
            assert report.summary.n_completed == len(reqs)
            outs[fused] = [report.outputs[r.rid] for r in reqs]
    assert outs[True] == outs[False], \
        "Pallas fused decode changed tokens vs the gather path"


# --------------------------------------------------------------------------- #
# Block-table invariants (properties; hypothesis drivers below)
# --------------------------------------------------------------------------- #


def _check_live_tables_disjoint(ops, slots):
    """Random admit/retire/grow: live block tables stay pairwise
    disjoint, mapped entries stay inside the physical grid, and the
    pool's own conservation checks hold — after EVERY op."""
    pool = KVCachePool(slots, 64, block_size=16, max_len=256)
    live, rid = [], 0
    for kind, arg in ops:
        if kind == "admit":
            n = 1 + arg % pool.kv_len
            if pool.fits(n):
                pool.admit(rid, n)
                live.append(rid)
                rid += 1
        elif kind == "retire" and live:
            pool.retire(live.pop(arg % len(live)))
        elif kind == "grow":
            pool.grow(min(pool.kv_len + 16 * (1 + arg % 4), pool.max_len))
        held: set[int] = set()
        for r in live:
            row = {p for p in pool.block_table(r) if p >= 0}
            assert row, "live lease with no mapped blocks"
            assert not (held & row), "two live tables share a block"
            assert max(row) < pool.allocator.num_blocks, \
                "table points past the physical grid"
            held |= row
        pool.check()


def _check_column_major_roundtrip(slots, nb, bs, pid, pos):
    """The column-major grid mapping round-trips: pid -> (row, offset)
    -> pid, and ``flat_position`` decomposes uniquely back into (row,
    block, in-block offset)."""
    from repro.kernels.paged_gather import flat_position

    t = nb * bs
    pid = pid % (slots * nb)
    pos = pos % t
    row, off = pid % slots, (pid // slots) * bs
    assert row + (off // bs) * slots == pid          # mapping round-trips
    flat = int(flat_position(np.int64(pid), np.int64(pos), slots, t, bs))
    assert flat == row * t + off + pos % bs
    # the flat index decomposes uniquely — no two (pid, pos%bs) collide
    assert (flat // t, (flat % t) // bs, flat % bs) \
        == (row, off // bs, pos % bs)
    # the quantized pool's scale cell is the SAME identity: a token's
    # flat cache index, divided by the block size, is its block's flat
    # scale index — codes and scales can never resolve different blocks
    assert (pid % slots) * nb + pid // slots == flat // bs


def _check_retired_scatter_drops(seed):
    """Scatter writes through the block table touch EXACTLY the mapped
    rows' leased positions: rows whose table entry is unmapped (-1 — a
    retired slot) or whose position overruns the table write NOTHING,
    and no other cache byte moves (no aliasing)."""
    import jax.numpy as jnp

    from repro.kernels.paged_gather import flat_position
    from repro.models.attention import _cache_write

    rng = np.random.default_rng(seed)
    b, t, g, d, bs = 3, 32, 2, 4, 8
    nb = t // bs
    cache = rng.standard_normal((b, t, g, d)).astype(np.float32)
    perm = list(rng.permutation(b * nb))
    tables = np.full((b, nb), -1, np.int64)
    for i in range(b):
        for j in range(int(rng.integers(0, nb + 1))):   # 0 => retired row
            tables[i, j] = perm.pop()
    pos = rng.integers(0, t, size=b)
    new = rng.standard_normal((b, g, d)).astype(np.float32)
    out = np.asarray(_cache_write(
        jnp.asarray(cache), jnp.asarray(new), jnp.asarray(pos),
        page_tables=jnp.asarray(tables), page_block=bs))

    expected = cache.reshape(b * t, g, d).copy()
    for i in range(b):
        pid = tables[i, pos[i] // bs]
        if pid >= 0:                      # mapped: exactly one row moves
            expected[int(flat_position(pid, pos[i], b, t, bs))] = new[i]
    np.testing.assert_array_equal(out.reshape(b * t, g, d), expected)


def _check_scales_never_alias_across_recycles(seed):
    """Random admit/retire traffic through an int8 pool: after every
    prompt write, the new lease's scale cells hold ONLY the new
    tenant's scales (prompt blocks) or zero (lease tail), and no other
    cell — live tenants' or free blocks' — moved at all.  A recycled
    block can therefore never dequantize through a previous tenant's
    scale."""
    import jax.numpy as jnp

    from repro.serve import get_adapter

    rng = np.random.default_rng(seed)
    adapter = get_adapter("dense")
    n_l, slots, bs, g, hd = 2, 2, 8, 2, 4
    kv_len = 32
    nb = kv_len // bs
    cache = {"k": jnp.zeros((n_l, slots, kv_len, g, hd), jnp.int8),
             "v": jnp.zeros((n_l, slots, kv_len, g, hd), jnp.int8),
             "k_scale": jnp.zeros((n_l, slots, nb, g), jnp.float32),
             "v_scale": jnp.zeros((n_l, slots, nb, g), jnp.float32),
             "pos": jnp.zeros((slots,), jnp.int32)}
    pool = KVCachePool(slots, kv_len, block_size=bs, max_len=kv_len)
    live, rid = [], 0
    for _ in range(12):
        if live and (rng.random() < 0.4 or pool.free_slots == 0):
            pool.retire(live.pop(rng.integers(len(live))))
            continue
        proj = int(rng.integers(1, kv_len + 1))
        if not pool.fits(proj):
            continue
        plen = int(rng.integers(1, proj + 1))
        lease = pool.admit(rid, proj)
        live.append(rid)
        rid += 1
        pid = np.asarray(lease.blocks)
        tok = np.arange(plen)
        p = pid[tok // bs]
        pm = jnp.asarray((p % slots) * kv_len + (p // slots) * bs
                         + tok % bs, jnp.int32)
        sm = ((pid % slots) * nb + pid // slots).astype(np.int32)
        vals = rng.standard_normal((n_l, 1, plen, g, hd)).astype(np.float32)
        row = {"k": jnp.asarray(vals), "v": jnp.asarray(vals),
               "pos": jnp.asarray(plen, jnp.int32)}
        before = np.asarray(cache["k_scale"]).reshape(n_l, slots * nb, g)
        cache = adapter.write_row(cache, lease.slot, row, plen, kv_len,
                                  page_map=pm, scale_map=sm,
                                  page_block=bs)
        after = np.asarray(cache["k_scale"]).reshape(n_l, slots * nb, g)
        npb = -(-plen // bs)
        pad = npb * bs - plen
        v = np.pad(vals[:, 0], ((0, 0), (0, pad), (0, 0), (0, 0)))
        want = np.abs(v.reshape(n_l, npb, bs, g, hd)).max((2, 4)) / 127.0
        np.testing.assert_allclose(after[:, sm[:npb]], want, rtol=1e-5,
                                   err_msg="prompt scales wrong")
        assert not after[:, sm[npb:]].any(), \
            "lease tail kept a previous tenant's scale"
        untouched = np.ones(slots * nb, bool)
        untouched[sm] = False
        np.testing.assert_array_equal(after[:, untouched],
                                      before[:, untouched],
                                      err_msg="scale write aliased "
                                              "outside the lease")


if HAVE_HYPOTHESIS:
    table_ops_st = st.lists(
        st.tuples(st.sampled_from(["admit", "retire", "grow"]),
                  st.integers(1, 100)),
        min_size=1, max_size=60)

    @settings(max_examples=100, deadline=None)
    @given(ops=table_ops_st, slots=st.integers(1, 8))
    def test_live_tables_stay_disjoint(ops, slots):
        _check_live_tables_disjoint(ops, slots)

    @settings(max_examples=200, deadline=None)
    @given(slots=st.integers(1, 16), nb=st.integers(1, 32),
           bs=st.sampled_from([1, 8, 16, 32]),
           pid=st.integers(0, 1 << 16), pos=st.integers(0, 1 << 16))
    def test_column_major_grid_roundtrips(slots, nb, bs, pid, pos):
        _check_column_major_roundtrip(slots, nb, bs, pid, pos)

    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(0, 1 << 30))
    def test_retired_scatter_writes_drop(seed):
        _check_retired_scatter_drops(seed)

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 1 << 30))
    def test_scales_never_alias_across_recycles(seed):
        _check_scales_never_alias_across_recycles(seed)


def test_table_invariants_seeded_sweep():
    """Hypothesis-free fallback: the same block-table properties over
    seeded random cases, so the invariants are always exercised."""
    rng = random.Random(11)
    for _ in range(25):
        ops = [(rng.choice(["admit", "retire", "grow"]),
                rng.randint(1, 100)) for _ in range(rng.randint(1, 60))]
        _check_live_tables_disjoint(ops, rng.randint(1, 8))
        _check_column_major_roundtrip(
            rng.randint(1, 16), rng.randint(1, 32),
            rng.choice([1, 8, 16, 32]),
            rng.randint(0, 1 << 16), rng.randint(0, 1 << 16))
    for seed in range(5):
        _check_retired_scatter_drops(seed)
    for seed in range(3):
        _check_scales_never_alias_across_recycles(seed)


# --------------------------------------------------------------------------- #
# Executed-plan pins: spy, HLO, byte-identical unpaged path
# --------------------------------------------------------------------------- #


def test_tuned_paged_block_reaches_executed_kernel(f32_cfg, monkeypatch):
    """The router-resolved fused ``block_s`` AND table geometry must
    reach the kernel call the engine actually runs — not just sit in the
    memoized plan."""
    import jax

    from repro.kernels import paged_decode_attention as pda_mod
    from repro.models import build_model

    seen = []
    real = pda_mod.paged_decode_attention

    def spy(q, kc, vc, tables, clen, **kw):
        seen.append((int(kw["block_s"]), int(kw["page_block"]),
                     int(tables.shape[-1])))
        return real(q, kc, vc, tables, clen, **kw)

    monkeypatch.setattr(pda_mod, "paged_decode_attention", spy)
    params = build_model(f32_cfg).init(jax.random.key(0))
    eng = ServeEngine(f32_cfg, slots=2, max_len=64, params=params,
                      tuning_cache=TuningCache(path=None))
    eng.submit([1, 2, 3], max_new_tokens=2)
    report = eng.run()
    assert report.summary.n_completed == 1
    plan = eng.router.resolve(eng.router.bucket(eng.pool.kv_len))
    geo = eng.router._geometry()
    assert seen, "decode ran without the fused paged sweep"
    assert set(seen) == {(plan.paged_decode_block, geo["page_block"],
                          geo["max_blocks_per_row"])}


def test_paged_block_changes_lowered_step_not_logits(f32_cfg):
    """Changing the tuned fused ``block_s`` changes the compiled step
    (the schedule the tuner decided) while the logits stay fixed — the
    acceptance criterion that the paged plan is observable in execution,
    not only in the cached decision."""
    import jax
    import jax.numpy as jnp

    from repro.launch.mesh import make_local_mesh
    from repro.launch.steps import make_decode_step
    from repro.models import build_model
    from repro.runtime import sharding as shd
    from repro.serve import get_adapter

    model = build_model(f32_cfg)
    params = model.init(jax.random.key(0))
    plan = shd.resolve_plan(f32_cfg, make_local_mesh(1, 1),
                            ShapeConfig("serve", 64, 2, "decode"))
    step = jax.jit(make_decode_step(model, plan),
                   static_argnames=("decode_block", "page_block",
                                    "paged_decode_block"))
    cache = get_adapter(f32_cfg.family).init_pool(model, 2, 64,
                                                  expand_kv=plan.expand_kv)
    cache["pos"] = jnp.asarray([5, 9], jnp.int32)
    toks = jnp.asarray([[3], [4]], jnp.int32)
    tables = jnp.asarray([[0, 2, -1, -1], [1, 3, -1, -1]], jnp.int32)

    hlo = {bs: step.lower(params, dict(cache), toks, page_tables=tables,
                          page_block=16, paged_decode_block=bs).as_text()
           for bs in (16, 32)}
    assert hlo[16] != hlo[32], \
        "paged_decode_block did not change the lowered step"
    l16, _ = step(params, dict(cache), toks, page_tables=tables,
                  page_block=16, paged_decode_block=16)
    l32, _ = step(params, dict(cache), toks, page_tables=tables,
                  page_block=16, paged_decode_block=32)
    np.testing.assert_allclose(np.asarray(l16), np.asarray(l32),
                               rtol=1e-4, atol=1e-4)


def test_unpaged_step_lowers_byte_identical_to_pre_pr_path(f32_cfg):
    """Without tables the decode step must route through exactly the
    code that existed before the fused kernel was threadable: identical
    lowering to a step that never mentions ``paged_decode_block``."""
    import jax
    import jax.numpy as jnp

    from repro.launch.mesh import make_local_mesh
    from repro.launch.steps import make_decode_step
    from repro.models import build_model
    from repro.runtime import sharding as shd
    from repro.serve import get_adapter

    model = build_model(f32_cfg)
    params = model.init(jax.random.key(0))
    plan = shd.resolve_plan(f32_cfg, make_local_mesh(1, 1),
                            ShapeConfig("serve", 64, 2, "decode"))
    step = jax.jit(make_decode_step(model, plan),
                   static_argnames=("decode_block", "page_block",
                                    "paged_decode_block"))
    cache = get_adapter(f32_cfg.family).init_pool(model, 2, 64,
                                                  expand_kv=plan.expand_kv)
    cache["pos"] = jnp.asarray([5, 9], jnp.int32)
    toks = jnp.asarray([[3], [4]], jnp.int32)

    # same jit name as `step`, pre-PR argument surface
    def decode_step(params, cache, tokens, decode_block=None):
        from repro.runtime.sharding import make_ctx
        return model.decode_step(params, cache, tokens,
                                 ctx=make_ctx(plan),
                                 decode_block=decode_block)

    plain = jax.jit(decode_step, static_argnames=("decode_block",))
    for db in (None, 256):
        new_hlo = step.lower(params, dict(cache), toks,
                             decode_block=db).as_text()
        old_hlo = plain.lower(params, dict(cache), toks,
                              decode_block=db).as_text()
        assert new_hlo == old_hlo, \
            f"unpaged lowering drifted from the pre-PR path (db={db})"
