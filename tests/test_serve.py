"""Serving subsystem invariants.

Property tests (hypothesis, matching tests/test_signature_props.py's
style) over the jax-free management layer — block aliasing, slot
recycling, FIFO no-starvation, bucket legality — plus a small end-to-end
check that the ragged decode pool is token-exact against the sequential
scalar-pos path.

Each property is a plain ``_check_*`` function: hypothesis drives it
when installed; a seeded random sweep covers the same invariants when it
is not (CI installs requirements-dev and runs both).
"""

import dataclasses
import random

import pytest

from repro.configs.base import ShapeConfig, get_config
from repro.core.hw import TPU_REGISTRY
from repro.serve import (BlockAllocator, BucketRouter, BucketSpec,
                         KVCachePool, Request, Scheduler)
from repro.tuner import TuningCache

HW = TPU_REGISTRY["cpu_sim"]

try:
    import hypothesis.strategies as st
    from hypothesis import given, settings
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


# --------------------------------------------------------------------------- #
# Properties (plain functions; drivers below)
# --------------------------------------------------------------------------- #


def _check_allocator_never_aliases(ops, num_blocks, block_size):
    """Slot recycling never aliases two live requests' blocks, blocks
    are conserved, and ownership stays in sync — after EVERY op."""
    a = BlockAllocator(num_blocks, block_size)
    live = []
    rid = 0
    for kind, arg in ops:
        if kind == "alloc":
            if a.can_alloc(arg):
                a.alloc(rid, arg)
                live.append(rid)
                rid += 1
        elif live:
            a.release(live.pop(arg % len(live)))
        a.check()
    assert set(a.holders()) == set(live)


def _check_pool_recycling(ops, slots):
    """Recycled slots are never double-booked; growth keeps leases."""
    pool = KVCachePool(slots, 64, block_size=8, max_len=8192)
    live = []
    rid = 0
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
            pool.grow(pool.kv_len + 8 * (1 + arg % 4))
        pool.check()
    assert pool.live == len(live)
    assert pool.free_slots == slots - len(live)


def _check_no_starvation_fifo(mix, slots, finish_flags):
    """Every submitted request completes (no starvation) and admission
    is strictly FIFO, under abstract decode ticks + early finishes."""
    pool = KVCachePool(slots, 64, block_size=8)
    sched = Scheduler(pool)
    reqs = [Request(prompt=[1] * p, max_new_tokens=o, arrival=float(i))
            for i, (p, o) in enumerate(mix)]
    for r in reqs:
        assert sched.submit(r)    # all fit one row: projected <= 32 < 64
    admitted_order = []
    t, guard = 0.0, 0
    flags = iter(finish_flags)
    while not sched.idle:
        guard += 1
        assert guard < 10_000, "scheduler livelocked"
        sched.poll(t)
        for r in sched.admissible():
            admitted_order.append(r.rid)
        finish_now = next(flags, False) if sched.live else False
        for r in list(sched.live):
            r.generated.append(0)
            if r.done or (finish_now and r is sched.live[0]):
                r.generated.extend(
                    [0] * (r.max_new_tokens - len(r.generated)))
                sched.finish(r)
        t += 1.0
    assert len(sched.completed) == len(reqs)          # nobody starved
    assert admitted_order == [r.rid for r in reqs]    # strict FIFO


def _check_bucket_quantization(n, mode, quantum):
    spec = BucketSpec(min_len=32, max_len=4096, mode=mode, quantum=quantum)
    q = spec.quantize(n)
    assert q >= n                          # a bucket always covers
    assert q <= spec.max_len               # and never exceeds the cap
    assert q in spec.lattice()             # and is on the finite lattice
    assert spec.quantize(q) == q           # quantization is idempotent
    with pytest.raises(ValueError):
        spec.quantize(spec.max_len + 1)


def _check_bucket_resolution_legal(need, slots):
    """Any lattice point resolves through the tuner to a legal kernel
    mapping; re-resolving is warm and zero-probe."""
    cfg = get_config("smollm-135m").reduced()
    router = BucketRouter(cfg, BucketSpec(min_len=32, max_len=2048),
                          slots=slots, hw=HW, cache=TuningCache(path=None))
    b = router.bucket(need)
    assert b.covers(slots, need)
    plan = router.resolve(b)
    assert plan.decode_block % 128 == 0 and 128 <= plan.decode_block <= 8192
    bq, bk = plan.prefill_blocks
    assert bq >= 8 and bk >= 128
    probes_before = router.stats.probes
    assert router.resolve(b) is plan           # router-level warm hit
    assert router.stats.probes == probes_before
    assert router.stats.warm >= 1


# --------------------------------------------------------------------------- #
# Hypothesis drivers
# --------------------------------------------------------------------------- #

if HAVE_HYPOTHESIS:
    ops_st = st.lists(st.tuples(st.sampled_from(["alloc", "free"]),
                                st.integers(1, 200)),
                      min_size=1, max_size=60)
    pool_ops_st = st.lists(
        st.tuples(st.sampled_from(["admit", "retire", "grow"]),
                  st.integers(1, 100)),
        min_size=1, max_size=60)
    mix_st = st.lists(st.tuples(st.integers(1, 24), st.integers(1, 8)),
                      min_size=1, max_size=25)

    @settings(max_examples=100, deadline=None)
    @given(ops=ops_st, num_blocks=st.integers(4, 64),
           block_size=st.integers(1, 32))
    def test_allocator_never_aliases(ops, num_blocks, block_size):
        _check_allocator_never_aliases(ops, num_blocks, block_size)

    @settings(max_examples=100, deadline=None)
    @given(ops=pool_ops_st, slots=st.integers(1, 8))
    def test_pool_recycling_invariants(ops, slots):
        _check_pool_recycling(ops, slots)

    @settings(max_examples=100, deadline=None)
    @given(mix=mix_st, slots=st.integers(1, 4),
           finish_flags=st.lists(st.booleans(), max_size=300))
    def test_no_starvation_and_fifo(mix, slots, finish_flags):
        _check_no_starvation_fifo(mix, slots, finish_flags)

    @settings(max_examples=200, deadline=None)
    @given(n=st.integers(1, 4096),
           mode=st.sampled_from(["pow2", "linear", "fixed"]),
           quantum=st.integers(8, 128))
    def test_bucket_quantization_covers_and_bounds(n, mode, quantum):
        _check_bucket_quantization(n, mode, quantum)

    @settings(max_examples=50, deadline=None)
    @given(need=st.integers(1, 2048), slots=st.integers(1, 16))
    def test_bucket_resolution_yields_legal_plan(need, slots):
        _check_bucket_resolution_legal(need, slots)


def test_invariants_seeded_sweep():
    """Hypothesis-free fallback: the same properties over seeded random
    cases, so the invariants are always exercised."""
    rng = random.Random(7)
    for _ in range(25):
        ops = [(rng.choice(["alloc", "free"]), rng.randint(1, 200))
               for _ in range(rng.randint(1, 60))]
        _check_allocator_never_aliases(ops, rng.randint(4, 64),
                                       rng.randint(1, 32))
        pops = [(rng.choice(["admit", "retire", "grow"]),
                 rng.randint(1, 100)) for _ in range(rng.randint(1, 60))]
        _check_pool_recycling(pops, rng.randint(1, 8))
        mix = [(rng.randint(1, 24), rng.randint(1, 8))
               for _ in range(rng.randint(1, 25))]
        flags = [rng.random() < 0.5 for _ in range(300)]
        _check_no_starvation_fifo(mix, rng.randint(1, 4), flags)
        _check_bucket_quantization(rng.randint(1, 4096),
                                   rng.choice(["pow2", "linear", "fixed"]),
                                   rng.randint(8, 128))
    for need, slots in [(1, 1), (200, 4), (2048, 16), (1000, 3)]:
        _check_bucket_resolution_legal(need, slots)


# --------------------------------------------------------------------------- #
# Deterministic scheduler/bucket behaviours
# --------------------------------------------------------------------------- #


def test_longer_request_waits_for_pool_growth():
    """A long request queued behind a short head must NOT be seated in
    rows that would truncate its cache — it waits for its turn at the
    head, when the engine grows the pool to its bucket."""
    pool = KVCachePool(2, 32, block_size=8, max_len=128)
    sched = Scheduler(pool)
    short = Request(prompt=[1] * 4, max_new_tokens=4)     # projected 8
    long_ = Request(prompt=[1] * 40, max_new_tokens=20)   # projected 60
    assert sched.submit(short) and sched.submit(long_)
    sched.poll(0.0)
    assert sched.admissible() == [short]
    assert sched.peek_need_len() == 60    # engine grows for the new head
    pool.grow(64)
    assert sched.admissible() == [long_]
    pool.check()


def test_oversize_request_rejected_at_submit():
    pool = KVCachePool(2, 32, block_size=8, total_blocks=4)  # 32 tokens total
    sched = Scheduler(pool)
    assert not sched.submit(Request(prompt=[1] * 40, max_new_tokens=8))
    assert sched.rejected and sched.idle


def test_gang_mode_admits_only_into_empty_pool():
    pool = KVCachePool(2, 64, block_size=8)
    sched = Scheduler(pool, mode="gang")
    for _ in range(4):
        sched.submit(Request(prompt=[1, 2], max_new_tokens=2, arrival=0.0))
    sched.poll(0.0)
    first = sched.admissible()
    assert len(first) == 2
    assert sched.admissible() == []       # pool busy: no recycling
    for r in first:
        r.generated = [0, 0]
        sched.finish(r)
    assert len(sched.admissible()) == 2   # empty again: next gang


def test_warm_bucket_is_zero_probe_across_routers():
    """A second router sharing the TuningCache answers the same bucket
    from the cache: zero refine probes (the serve_bench criterion)."""
    cfg = get_config("smollm-135m").reduced()
    cache = TuningCache(path=None)
    spec = BucketSpec(min_len=32, max_len=512)
    r1 = BucketRouter(cfg, spec, slots=4, hw=HW, cache=cache)
    r1.resolve(r1.bucket(200))
    assert r1.stats.probes > 0                 # cold: refined
    r2 = BucketRouter(cfg, spec, slots=4, hw=HW, cache=cache)
    r2.resolve(r2.bucket(200))
    assert r2.stats.probes == 0                # warm: pure cache hits
    assert r2.stats.cache_hits == 2            # decode + prefill kernels


# --------------------------------------------------------------------------- #
# End-to-end: the ragged pool is token-exact vs the sequential path
# --------------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def f32_cfg():
    return dataclasses.replace(get_config("smollm-135m").reduced(),
                               dtype="float32")


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


def test_engine_matches_sequential_decode(f32_cfg):
    """Slot recycling + per-row positions must not change anyone's
    tokens: a 2-slot pool over 4 ragged requests reproduces the
    one-request-at-a-time scalar-pos outputs exactly."""
    import jax

    from repro.models import build_model
    from repro.serve import ServeEngine

    prompts = [[7, 3, 99], [11, 5, 2, 42, 17, 101, 9], [250, 1],
               [33, 44, 55, 66]]
    max_new = 4
    params = build_model(f32_cfg).init(jax.random.key(0))
    ref = _sequential_reference(f32_cfg, params, prompts, max_new)

    eng = ServeEngine(f32_cfg, slots=2, max_len=64, params=params,
                      tuning_cache=TuningCache(path=None))
    reqs = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
    report = eng.run()
    assert report.summary.n_completed == len(prompts)
    for req, p, expected in zip(reqs, prompts, ref):
        assert report.outputs[req.rid][len(p):] == expected
    # 4 requests through 2 slots: recycling happened, shapes stayed put
    assert report.compiled_decode_shapes == 1
    assert report.router_stats["probes"] > 0          # cold buckets refined


def test_reset_freezes_the_warm_heap(f32_cfg):
    """``reset`` moves what is live (the warm machinery) out of the
    cyclic collector's reach, and an engine served again after it gives
    the same tokens."""
    import gc

    import jax

    from repro.models import build_model
    from repro.serve import ServeEngine

    params = build_model(f32_cfg).init(jax.random.key(0))
    eng = ServeEngine(f32_cfg, slots=2, max_len=64, params=params,
                      tuning_cache=TuningCache(path=None))
    prompts = [[7, 3, 99], [11, 5, 2, 42, 17]]
    outs = []
    try:
        for _ in range(2):
            reqs = [eng.submit(p, max_new_tokens=3) for p in prompts]
            report = eng.run()
            outs.append([report.outputs[r.rid] for r in reqs])
            frozen = gc.get_freeze_count()
            eng.reset()
            assert gc.get_freeze_count() > frozen
            assert not eng.outputs and not eng.scheduler.live
    finally:
        gc.unfreeze()
    assert outs[0] == outs[1]


@pytest.mark.parametrize("arch", ["mamba2-1.3b", "zamba2-7b",
                                  "whisper-medium"])
def test_engine_matches_sequential_decode_families(arch):
    """The CacheAdapter pool is token-exact for the recurrent, hybrid,
    and encoder-decoder families too — slot recycling, bucket-padded
    (or, for ssm, exact-length) prefill, and per-row positions never
    change anyone's tokens vs the one-request-at-a-time path."""
    import jax

    from repro.models import build_model
    from repro.serve import ServeEngine

    cfg = dataclasses.replace(get_config(arch).reduced(), dtype="float32")
    prompts = [[7, 3, 99], [11, 5, 2, 42, 17, 101, 9], [250, 1],
               [33, 44, 55, 66]]
    max_new = 4
    params = build_model(cfg).init(jax.random.key(0))
    ref = _sequential_reference(cfg, params, prompts, max_new)

    # whole-prompt prefill: the pin is BITWISE equality with the
    # one-request-at-a-time reference; the chunked default reorders
    # float accumulation in the hybrid recurrence (argmax flips on
    # random-init weights) — chunked parity has its own suite
    eng = ServeEngine(cfg, slots=2, max_len=64, params=params,
                      prefill_chunk=None,
                      tuning_cache=TuningCache(path=None))
    reqs = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
    report = eng.run()
    assert report.summary.n_completed == len(prompts)
    for req, p, expected in zip(reqs, prompts, ref):
        assert report.outputs[req.rid][len(p):] == expected
    if cfg.is_attention_free:
        # a length-free cache never recompiles, no matter the traffic
        assert report.compiled_decode_shapes == 1


# --------------------------------------------------------------------------- #
# The tuned decode_block is consumed by the EXECUTED decode step
# --------------------------------------------------------------------------- #


def test_tuned_decode_block_parameterizes_executed_step(f32_cfg, monkeypatch):
    """The bucket-resolved ``decode_block`` must reach the attention
    sweep the engine actually runs — not just sit in the memoized plan."""
    import jax

    from repro.models import attention as attn_mod
    from repro.models import build_model
    from repro.serve import ServeEngine

    seen = []
    real = attn_mod.blocked_decode_attention

    def spy(*args, **kw):
        seen.append(int(kw["block"]))
        return real(*args, **kw)

    monkeypatch.setattr(attn_mod, "blocked_decode_attention", spy)
    params = build_model(f32_cfg).init(jax.random.key(0))
    # paged=False: the paged default reads through the FUSED kernel and
    # never reaches blocked_decode_attention — its executed-plan pin
    # lives in tests/test_paged_decode.py
    eng = ServeEngine(f32_cfg, slots=2, max_len=64, params=params,
                      paged=False, tuning_cache=TuningCache(path=None))
    eng.submit([1, 2, 3], max_new_tokens=2)
    report = eng.run()
    assert report.summary.n_completed == 1
    plan = eng.router.resolve(eng.router.bucket(eng.pool.kv_len))
    assert seen, "decode ran without the tuned attention sweep"
    assert set(seen) == {plan.decode_block}


def test_decode_block_changes_executed_step_not_tokens(f32_cfg):
    """Changing the tuned block changes the compiled kernel invocation
    (the mapping/schedule) while the math — and thus the tokens — stays
    fixed: the acceptance criterion that tuning is observable in
    execution rather than only in the cached decision."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.mesh import make_local_mesh
    from repro.launch.steps import make_decode_step
    from repro.models import build_model
    from repro.runtime import sharding as shd
    from repro.serve import get_adapter

    model = build_model(f32_cfg)
    params = model.init(jax.random.key(0))
    plan = shd.resolve_plan(f32_cfg, make_local_mesh(1, 1),
                            ShapeConfig("serve", 256, 2, "decode"))
    step = jax.jit(make_decode_step(model, plan),
                   static_argnames=("decode_block",))
    cache = get_adapter(f32_cfg.family).init_pool(model, 2, 256,
                                                  expand_kv=plan.expand_kv)
    cache["pos"] = jnp.asarray([5, 9], jnp.int32)
    toks = jnp.asarray([[3], [4]], jnp.int32)

    hlo = {b: step.lower(params, dict(cache), toks,
                         decode_block=b).as_text() for b in (128, 256)}
    assert hlo[128] != hlo[256], \
        "decode_block did not change the lowered step"
    l128, _ = step(params, dict(cache), toks, decode_block=128)
    l256, _ = step(params, dict(cache), toks, decode_block=256)
    np.testing.assert_allclose(np.asarray(l128), np.asarray(l256),
                               rtol=1e-4, atol=1e-4)


def test_decode_block_reaches_pallas_kernel(f32_cfg, monkeypatch):
    """Under a Pallas-capable mode the tuned block arrives at the actual
    kernel call (``block_s=``), closing ROADMAP's 'decision only' gap."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import decode_attention as dak
    from repro.kernels import ops
    from repro.models import build_model
    from repro.serve import get_adapter

    seen = []
    real = dak.decode_attention_pallas

    def spy(*args, **kw):
        seen.append(int(kw["block_s"]))
        return real(*args, **kw)

    monkeypatch.setattr(dak, "decode_attention_pallas", spy)
    model = build_model(f32_cfg)
    params = model.init(jax.random.key(0))
    cache = get_adapter(f32_cfg.family).init_pool(model, 1, 128)
    cache["pos"] = jnp.asarray([6], jnp.int32)
    with ops.force("interpret"):
        logits, _ = model.decode_step(params, cache,
                                      jnp.asarray([[3]], jnp.int32),
                                      decode_block=128)
    assert seen and set(seen) == {128}
    assert np.isfinite(np.asarray(logits)).all()
