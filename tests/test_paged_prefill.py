"""Executed prefill flash tiles + physical KV paging (PR 5 acceptance).

Two pin families, mirroring PR 4's decode pins:

  * prefill-tile consumption — the BucketRouter-resolved (block_q,
    block_k) reaches the attention sweep the engine actually RUNS (spy),
    changing the tiles changes the lowered prefill while the logits stay
    fixed, and ``prefill_tiles=None`` lowers byte-identically to the
    GSPMD path that existed before the tiles were threadable;
  * physical block tables — the paged gather is exactly the dense read
    (token-exact), recycling never aliases two live requests' tables,
    and the ragged pool stays token-exact against the sequential decode
    path for ALL FIVE families with ``paged=True``.
"""

import dataclasses

import numpy as np
import pytest

from repro.configs.base import ShapeConfig, get_config
from repro.serve import ServeEngine
from repro.tuner import TuningCache


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
                                   jnp.asarray([[out[-1]]], jnp.int32))[:2]
            lg = logits[:, 0] if logits.ndim == 3 else logits
            out.append(int(jnp.argmax(lg[0])))
        outs.append(out)
    return outs


# --------------------------------------------------------------------------- #
# Prefill tiles are consumed by the EXECUTED prefill
# --------------------------------------------------------------------------- #


def test_prefill_tiles_reach_executed_flash(f32_cfg, monkeypatch):
    """The router-resolved prompt-bucket tiles must reach the attention
    sweep the engine's prefill actually runs — not just sit in the
    memoized plan (the PR 4 criterion, now for prefill)."""
    import jax

    from repro.models import attention as attn_mod
    from repro.models import build_model

    seen = []
    real = attn_mod.tiled_prefill_attention

    def spy(*args, **kw):
        seen.append((int(kw["block_q"]), int(kw["block_k"])))
        return real(*args, **kw)

    monkeypatch.setattr(attn_mod, "tiled_prefill_attention", spy)
    params = build_model(f32_cfg).init(jax.random.key(0))
    # whole-prompt prefill: the pin is the tiled WHOLE-PROMPT sweep; the
    # chunked default consumes the tuned tile as its chunk width instead
    # (masked decode-style writes — test_chunked_prefill covers that)
    eng = ServeEngine(f32_cfg, slots=2, max_len=64, params=params,
                      prefill_chunk=None,
                      tuning_cache=TuningCache(path=None))
    eng.submit([1, 2, 3], max_new_tokens=2)
    report = eng.run()
    assert report.summary.n_completed == 1
    pb = eng.router.quantize_prompt(3)
    assert seen, "prefill ran without the tuned tile sweep"
    assert set(seen) == {eng.router.prefill_tiles(pb)}


def test_prefill_tiles_change_lowered_step_not_logits(f32_cfg):
    """Changing the tiles changes the compiled prefill (the schedule the
    tuner decided) while the logits stay fixed — and ``None`` keeps the
    GSPMD path BYTE-IDENTICAL to a prefill that never saw the tiles
    argument at all."""
    import jax
    import jax.numpy as jnp

    from repro.launch.mesh import make_local_mesh
    from repro.launch.steps import make_prefill_step
    from repro.models import build_model
    from repro.runtime import sharding as shd

    model = build_model(f32_cfg)
    params = model.init(jax.random.key(0))
    plan = shd.resolve_plan(f32_cfg, make_local_mesh(1, 1),
                            ShapeConfig("serve", 32, 1, "decode"))
    step = jax.jit(make_prefill_step(model, plan, None),
                   static_argnames=("prefill_tiles",))
    batch = {"tokens": jnp.asarray([[5, 7, 11, 13, 17, 19, 23, 29] * 4],
                                   jnp.int32)}
    last = jnp.asarray([31], jnp.int32)

    hlo = {t: step.lower(params, batch, last, prefill_tiles=t).as_text()
           for t in ((8, 128), (16, 256))}
    assert hlo[(8, 128)] != hlo[(16, 256)], \
        "prefill tiles did not change the lowered step"

    l_a, _ = step(params, batch, last, prefill_tiles=(8, 128))
    l_b, _ = step(params, batch, last, prefill_tiles=(16, 256))
    np.testing.assert_allclose(np.asarray(l_a), np.asarray(l_b),
                               rtol=1e-4, atol=1e-4)

    # None must route through exactly the code the GSPMD path always ran:
    # identical lowering to a step that does not thread tiles at all
    def prefill_step(params, batch, last_pos):   # same jit name as `step`
        from repro.runtime.sharding import make_ctx
        return model.prefill(params, batch, batch["tokens"].shape[1],
                             last_pos=last_pos, ctx=make_ctx(plan))

    none_hlo = step.lower(params, batch, last, prefill_tiles=None).as_text()
    plain_hlo = jax.jit(prefill_step).lower(params, batch, last).as_text()
    assert none_hlo == plain_hlo, \
        "prefill_tiles=None altered the GSPMD prefill lowering"
    l_none, _ = step(params, batch, last, prefill_tiles=None)
    np.testing.assert_allclose(np.asarray(l_none), np.asarray(l_a),
                               rtol=1e-4, atol=1e-4)


def test_prefill_tiles_reach_pallas_kernel(f32_cfg, monkeypatch):
    """Under a Pallas-capable mode the tuned tiles arrive at the actual
    flash kernel call (``plan.block_q/block_k``), closing ROADMAP's
    'prefill tiles are decisions only' gap."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import flash_attention as fa_mod
    from repro.kernels import ops
    from repro.models import build_model

    seen = []
    real = fa_mod.flash_attention_pallas

    def spy(*args, **kw):
        seen.append((int(kw["plan"].block_q), int(kw["plan"].block_k)))
        return real(*args, **kw)

    monkeypatch.setattr(fa_mod, "flash_attention_pallas", spy)
    model = build_model(f32_cfg)
    params = model.init(jax.random.key(0))
    batch = {"tokens": jnp.asarray([[5, 7, 11, 13, 17, 19, 23, 29]],
                                   jnp.int32)}
    ref, _ = model.prefill(params, batch, 8)
    with ops.force("interpret"):
        logits, _ = model.prefill(params, batch, 8, prefill_tiles=(8, 128))
    assert seen and set(seen) == {(8, 128)}
    np.testing.assert_allclose(np.asarray(ref), np.asarray(logits),
                               rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------- #
# Physical block tables
# --------------------------------------------------------------------------- #


def test_paged_gather_matches_dense_read():
    """The gather-by-block-table read is EXACTLY the dense read: for any
    block permutation, gathering the physical store through the tables
    reproduces the logical rows bit-for-bit (it is a pure copy), in both
    the reference and the Pallas (interpret) kernel."""
    import jax.numpy as jnp

    from repro.kernels.paged_gather import (paged_gather_pallas,
                                            paged_gather_ref)

    rng = np.random.default_rng(0)
    b, t, g, d, bs = 3, 64, 2, 8, 16
    nb = t // bs
    logical = rng.standard_normal((b, t, g, d)).astype(np.float32)
    # scatter the logical blocks into a permuted physical grid
    pids = rng.permutation(b * nb).reshape(b, nb)
    physical = np.zeros_like(logical)
    for row in range(b):
        for j in range(nb):
            pid = pids[row, j]
            prow, poff = pid % b, (pid // b) * bs
            physical[prow, poff:poff + bs] = logical[row, j * bs:(j + 1) * bs]
    tables = jnp.asarray(pids, jnp.int32)
    cache = jnp.asarray(physical)
    np.testing.assert_array_equal(
        np.asarray(paged_gather_ref(cache, tables, bs)), logical)
    np.testing.assert_array_equal(
        np.asarray(paged_gather_pallas(cache, tables, bs, interpret=True)),
        logical)


def test_block_tables_never_alias_across_recycling(f32_cfg):
    """Slot recycling re-points block tables; at every completion (and
    at the end) the LIVE rows' physical blocks must be pairwise disjoint
    — the aliasing invariant, now load-bearing for real cache bytes."""
    import jax

    from repro.models import build_model

    params = build_model(f32_cfg).init(jax.random.key(0))
    eng = ServeEngine(f32_cfg, slots=2, max_len=64, params=params,
                      paged=True, tuning_cache=TuningCache(path=None))

    def check_disjoint(req, now):
        held: set[int] = set()
        for r in eng.scheduler.live:
            mine = {int(p) for p in eng._tables[r.slot] if p >= 0}
            assert mine, f"live request {r.rid} has an unmapped table"
            assert not (held & mine), "block aliased by two live tables"
            held |= mine
        eng.pool.check()

    reqs = [eng.submit([1 + i] * (3 + 2 * i), max_new_tokens=3)
            for i in range(5)]
    report = eng.run(on_complete=check_disjoint)
    assert report.summary.n_completed == len(reqs)
    # retired slots are unmapped: a stale tenant can never write again
    assert (eng._tables == -1).all()


@pytest.mark.parametrize("arch", ["smollm-135m", "deepseek-moe-16b",
                                  "mamba2-1.3b", "zamba2-7b",
                                  "whisper-medium"])
def test_paged_engine_matches_sequential_decode(arch):
    """With physical block tables enabled, the ragged pool stays
    token-exact against the one-request-at-a-time scalar-pos path for
    every CacheAdapter family — scatter writes, gather reads, and block
    recycling never change anyone's tokens.  (For the attention-free ssm
    family paging is pure block accounting; the pin is that enabling it
    is still harmless end to end.)"""
    import jax

    from repro.models import build_model

    cfg = dataclasses.replace(get_config(arch).reduced(), dtype="float32")
    prompts = [[7, 3, 99], [11, 5, 2, 42, 17, 101, 9], [250, 1]]
    max_new = 3
    params = build_model(cfg).init(jax.random.key(0))
    ref = _sequential_reference(cfg, params, prompts, max_new)

    # whole-prompt prefill: the pin is BITWISE token equality with a
    # one-request-at-a-time reference, so the chunked default's
    # float-reordering (argmax flips on random-init weights for the
    # hybrid family) is opted out — chunked parity has its own suite
    eng = ServeEngine(cfg, slots=2, max_len=64, params=params, paged=True,
                      prefill_chunk=None,
                      tuning_cache=TuningCache(path=None))
    reqs = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
    report = eng.run()
    assert report.summary.n_completed == len(prompts)
    for req, p, expected in zip(reqs, prompts, ref):
        assert report.outputs[req.rid][len(p):] == expected


def test_paged_pool_rejects_illegal_geometry(f32_cfg):
    """Paged mode guards its physical grid: non-block-multiple lattice
    lengths and block budgets beyond the grid are config errors, not
    silent corruption."""
    from repro.serve import BucketSpec

    with pytest.raises(ValueError, match="divisible"):
        ServeEngine(f32_cfg, slots=2, max_len=48, paged=True,
                    block_size=32,
                    spec=BucketSpec(min_len=48, max_len=48, mode="fixed"),
                    tuning_cache=TuningCache(path=None))
    # a mid-lattice length that is not a block multiple must fail at
    # CONSTRUCTION, not at the mid-run growth that would first hit it
    with pytest.raises(ValueError, match="divisible"):
        ServeEngine(f32_cfg, slots=2, max_len=96, paged=True,
                    block_size=16,
                    spec=BucketSpec(min_len=48, max_len=96, mode="linear",
                                    quantum=24),
                    tuning_cache=TuningCache(path=None))
    # exact mode has no finite lattice: paging cannot pre-validate it
    with pytest.raises(ValueError, match="finite"):
        ServeEngine(f32_cfg, slots=2, max_len=64, paged=True,
                    spec=BucketSpec(min_len=32, max_len=64, mode="exact"),
                    tuning_cache=TuningCache(path=None))
    with pytest.raises(ValueError, match="exceeds the physical"):
        ServeEngine(f32_cfg, slots=2, max_len=64, paged=True,
                    total_blocks=64, tuning_cache=TuningCache(path=None))
