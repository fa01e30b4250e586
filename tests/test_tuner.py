"""Tuner subsystem: signature stability, cache round-trip + stats,
TUNED dispatch (warm hit == zero refine probes), and the clean fallback
when a kernel has no cost model."""

import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hw import TPU_REGISTRY
from repro.core.mapper import BlockPlan, MappingPolicy, plan_vector_blocks
from repro.core.workload import vecadd as vecadd_workload
from repro.kernels import ops, ref
from repro.tuner import (KERNEL_REGISTRY, SCHEMA_VERSION, KernelSpec,
                         TuningCache, WorkloadSignature, hardware_key,
                         register_kernel, resolve_mesh_plan, resolve_plan,
                         set_default_cache, tuned_call, workload_signature)

HW = TPU_REGISTRY["cpu_sim"]


@pytest.fixture(autouse=True)
def _isolated_default_cache():
    """Never let tests touch the user-level cache file."""
    set_default_cache(TuningCache(path=None))
    yield
    set_default_cache(None)


# --------------------------------------------------------------------------- #
# Signatures
# --------------------------------------------------------------------------- #


def test_signature_stable_across_equivalent_descriptions():
    x = jnp.zeros((128, 64), jnp.float32)
    a = workload_signature("k", shapes=[x, (32,)], dtypes=[x, "int32"],
                           policy=MappingPolicy.TUNED, causal=True, win=128)
    b = workload_signature("k", shapes=[(128, 64), 32],
                           dtypes=[np.float32, np.dtype("int32")],
                           policy="tuned", win=128, causal=True)
    assert a == b and a.key == b.key


def test_signature_distinguishes_workloads():
    base = workload_signature("k", shapes=[(128,)], dtypes=["float32"])
    assert base.key != workload_signature(
        "k", shapes=[(256,)], dtypes=["float32"]).key
    assert base.key != workload_signature(
        "k", shapes=[(128,)], dtypes=["bfloat16"]).key
    assert base.key != workload_signature(
        "k2", shapes=[(128,)], dtypes=["float32"]).key
    assert base.key != workload_signature(
        "k", shapes=[(128,)], dtypes=["float32"], flag=1).key


def test_signature_json_roundtrip():
    """as_dict/from_dict survive JSON bit-exactly (the hypothesis sweep
    over this lives in test_signature_props.py)."""
    sig = workload_signature("k", shapes=[(128, 64), 32],
                             dtypes=["float32", "int32"],
                             policy=MappingPolicy.TUNED, causal=True, win=128)
    back = WorkloadSignature.from_dict(json.loads(json.dumps(sig.as_dict())))
    assert back == sig and back.key == sig.key


def test_hardware_key_distinguishes_parts():
    assert hardware_key(TPU_REGISTRY["cpu_sim"]) \
        != hardware_key(TPU_REGISTRY["tpu_v5e"])
    assert hardware_key(HW) != hardware_key(HW.with_chips(4))
    assert hardware_key(HW) == hardware_key(TPU_REGISTRY["cpu_sim"])


# --------------------------------------------------------------------------- #
# Cache
# --------------------------------------------------------------------------- #


def _sig(n=4096) -> WorkloadSignature:
    return workload_signature("vecadd", shapes=[(n,)], dtypes=["float32"])


def test_cache_roundtrip_through_disk(tmp_path):
    path = str(tmp_path / "cache.json")
    c1 = TuningCache(path)
    c1.put(hardware_key(HW), _sig(), {"value": 2048}, cost=1e-5, probes=7)

    c2 = TuningCache(path)
    entry = c2.get(hardware_key(HW), _sig())
    assert entry is not None
    assert entry["plan"] == {"value": 2048}
    assert entry["cost"] == pytest.approx(1e-5)
    assert entry["probes"] == 7


def test_cache_version_mismatch_discards_file(tmp_path):
    path = str(tmp_path / "cache.json")
    c1 = TuningCache(path)
    c1.put(hardware_key(HW), _sig(), {"value": 2048})
    blob = json.load(open(path))
    blob["version"] = SCHEMA_VERSION + 1
    json.dump(blob, open(path, "w"))
    assert len(TuningCache(path)) == 0


def test_cache_corrupt_file_is_ignored(tmp_path):
    path = str(tmp_path / "cache.json")
    open(path, "w").write("{not json")
    c = TuningCache(path)
    assert len(c) == 0
    c.put(hardware_key(HW), _sig(), {"value": 1024})   # and still writable
    assert TuningCache(path).get(hardware_key(HW), _sig()) is not None


def test_cache_stats_and_lru_eviction():
    c = TuningCache(path=None, capacity=2)
    hk = hardware_key(HW)
    assert c.get(hk, _sig(1)) is None
    c.put(hk, _sig(1), {"value": 1})
    c.put(hk, _sig(2), {"value": 2})
    assert c.get(hk, _sig(1)) is not None     # refreshes 1 -> 2 is LRU
    c.put(hk, _sig(3), {"value": 3})          # evicts 2
    assert c.get(hk, _sig(2)) is None
    assert c.get(hk, _sig(1)) is not None
    s = c.stats
    assert (s.hits, s.misses, s.puts, s.evictions) == (2, 2, 3, 1)
    assert 0 < s.hit_rate < 1


def test_cache_concurrent_writers_merge(tmp_path):
    path = str(tmp_path / "cache.json")
    hk = hardware_key(HW)

    def writer(i):
        c = TuningCache(path)
        c.put(hk, _sig(1000 + i), {"value": i})

    threads = [threading.Thread(target=writer, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    merged = TuningCache(path)
    for i in range(8):
        assert merged.get(hk, _sig(1000 + i)) is not None, i


# --------------------------------------------------------------------------- #
# Dispatch: TUNED policy
# --------------------------------------------------------------------------- #


def test_tuned_warm_hit_spends_zero_probes():
    """Acceptance criterion: second identical dispatch is a pure cache hit."""
    cache = TuningCache(path=None)
    x = jnp.arange(5001, dtype=jnp.float32)
    y = 2.0 * x

    out = tuned_call("vecadd", x, y, hw=HW, cache=cache, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(3.0 * x))
    cold = (cache.stats.misses, cache.stats.refine_probes)
    assert cold[0] == 1 and cold[1] > 0   # the miss actually refined

    out = tuned_call("vecadd", x, y, hw=HW, cache=cache, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(3.0 * x))
    assert cache.stats.hits == 1
    assert cache.stats.misses == cold[0]            # no new miss
    assert cache.stats.refine_probes == cold[1]     # ZERO new probes


def test_tuned_plan_matches_across_processes(tmp_path):
    """The refined plan survives the disk round-trip bit-exactly."""
    path = str(tmp_path / "cache.json")
    desc = {"n": 100_000, "dtype": "float32", "dtype_bytes": 4}

    p1, i1 = resolve_plan("vecadd", HW, MappingPolicy.TUNED, desc,
                          TuningCache(path))
    p2, i2 = resolve_plan("vecadd", HW, MappingPolicy.TUNED, desc,
                          TuningCache(path))
    assert i1.source == "refined" and i2.source == "cache"
    assert i2.probes == 0
    assert p1 == p2


def test_tuned_resolves_distinct_plans_per_hardware():
    cache = TuningCache(path=None)
    desc = {"n": 1 << 22, "dtype": "float32", "dtype_bytes": 4}
    _, i1 = resolve_plan("vecadd", HW, MappingPolicy.TUNED, desc, cache)
    _, i2 = resolve_plan("vecadd", TPU_REGISTRY["tpu_v4"],
                         MappingPolicy.TUNED, desc, cache)
    assert i1.source == i2.source == "refined"      # no cross-hw hit
    assert len(cache) == 2


def test_non_tuned_policies_bypass_cache():
    cache = TuningCache(path=None)
    x = jnp.arange(2048, dtype=jnp.float32)
    for pol in (MappingPolicy.NAIVE, MappingPolicy.FIXED, MappingPolicy.AUTO):
        out = tuned_call("vecadd", x, x, hw=HW, policy=pol, cache=cache,
                         interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(2.0 * x))
    assert len(cache) == 0
    assert cache.stats.hits == cache.stats.misses == 0


def test_tuned_plan_never_beats_cost_of_seed():
    desc = {"n": 123_456, "dtype": "float32", "dtype_bytes": 4}
    _, info = resolve_plan("vecadd", HW, MappingPolicy.TUNED, desc,
                           TuningCache(path=None))
    assert info.cost is not None and info.seed_cost is not None
    assert info.cost <= info.seed_cost


def test_paged_decode_cost_charges_one_launch_per_chunk():
    """The fused paged decode runs one grid step per ``block_s`` chunk of
    a row (its pages are copied inside the step), so the cost model's
    launch term counts chunks, not pages."""
    from repro.tuner.dispatch import _launch_s, _roofline_s

    hw = TPU_REGISTRY["tpu_v5e"]
    desc = {"s": 2048, "d": 64, "page_block": 16, "max_blocks_per_row": 128,
            "dtype": "bfloat16", "dtype_bytes": 2}
    cost = KERNEL_REGISTRY["paged_decode"].cost_model(desc, hw)
    for block in (16, 256, 2048):
        chunks = 2048 // block
        streamed = _roofline_s(2048 * 4.0 * 64, 2048 * 2.0 * 64 * 2, hw)
        assert cost(block) == pytest.approx(streamed + _launch_s(chunks, hw))


def test_tuned_fallback_without_cost_model():
    """A kernel with no cost model returns the Eq. 1 seed, cached, no error."""
    spec = KERNEL_REGISTRY["vecadd"]
    register_kernel(KernelSpec(
        name="_nocost", describe=spec.describe, sig=spec.sig,
        seed_plan=spec.seed_plan, plan_value=spec.plan_value,
        plan_from_value=spec.plan_from_value, cost_model=None,
        candidates=spec.candidates, run=spec.run))
    try:
        cache = TuningCache(path=None)
        desc = {"n": 4096, "dtype": "float32", "dtype_bytes": 4}
        plan, info = resolve_plan("_nocost", HW, MappingPolicy.TUNED, desc,
                                  cache)
        assert info.source == "fallback" and info.probes == 0
        assert isinstance(plan, BlockPlan)
        assert plan == plan_vector_blocks(
            vecadd_workload(4096, dtype_bytes=4), HW, MappingPolicy.TUNED)
        _, info2 = resolve_plan("_nocost", HW, MappingPolicy.TUNED, desc,
                                cache)
        assert info2.source == "cache" and info2.probes == 0
    finally:
        del KERNEL_REGISTRY["_nocost"]


def test_mesh_tier_tuned_fallback():
    """TUNED at the mesh tier == AUTO plan, memoized with zero probes."""
    cache = TuningCache(path=None)
    auto = resolve_mesh_plan(256, 8, 1e6, 1e9, hw=HW,
                             policy=MappingPolicy.AUTO, cache=cache)
    tuned = resolve_mesh_plan(256, 8, 1e6, 1e9, hw=HW,
                              policy=MappingPolicy.TUNED, cache=cache)
    again = resolve_mesh_plan(256, 8, 1e6, 1e9, hw=HW,
                              policy=MappingPolicy.TUNED, cache=cache)
    assert tuned.num_microbatches == auto.num_microbatches
    assert again == tuned
    assert cache.stats.hits == 1 and cache.stats.refine_probes == 0


# --------------------------------------------------------------------------- #
# Dispatch: every registered kernel stays correct under TUNED
# --------------------------------------------------------------------------- #


def test_all_registered_kernels_correct_under_tuned():
    cache = TuningCache(path=None)
    k = jax.random.key

    x = jax.random.normal(k(0), (3000,))
    got = tuned_call("vecadd", x, x, hw=HW, cache=cache, interpret=True)
    np.testing.assert_allclose(got, ref.vecadd(x, x), rtol=1e-5)

    a = jnp.float32(1.7)
    got = tuned_call("saxpy", a, x, x, hw=HW, cache=cache, interpret=True)
    np.testing.assert_allclose(got, ref.saxpy(a, x, x), rtol=1e-5)

    A = jax.random.normal(k(1), (160, 96))
    B = jax.random.normal(k(2), (96, 130))
    got = tuned_call("matmul", A, B, hw=HW, cache=cache, interpret=True)
    np.testing.assert_allclose(got, ref.matmul(A, B), rtol=1e-4, atol=1e-4)

    q = jax.random.normal(k(3), (130, 64)) * 0.2
    kk = jax.random.normal(k(4), (130, 64)) * 0.2
    v = jax.random.normal(k(5), (130, 64))
    got = tuned_call("flash_attention", q, kk, v, hw=HW, cache=cache,
                     interpret=True, causal=True)
    want = ref.attention_chunked(q, kk, v, causal=True)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)

    xr = jax.random.normal(k(6), (100, 256))
    g = jax.random.normal(k(7), (256,))
    got = tuned_call("rmsnorm", xr, g, hw=HW, cache=cache, interpret=True)
    np.testing.assert_allclose(got, ref.rmsnorm(xr, g, 1e-6),
                               rtol=1e-4, atol=1e-4)

    qd = jax.random.normal(k(8), (64,)) * 0.2
    kc = jax.random.normal(k(9), (300, 64)) * 0.2
    vc = jax.random.normal(k(10), (300, 64))
    got = tuned_call("decode_attention", qd, kc, vc, 200, hw=HW, cache=cache,
                     interpret=True)
    want = ref.decode_attention(qd, kc, vc, jnp.int32(200))
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)

    img = jax.random.normal(k(11), (64, 128))
    got = tuned_call("gaussian_blur", img, hw=HW, cache=cache, interpret=True)
    np.testing.assert_allclose(got, ref.gaussian_blur(img, 5, 1.0),
                               rtol=1e-4, atol=1e-4)

    adj = (jax.random.uniform(k(12), (96, 96)) < 0.1).astype(jnp.float32)
    feats = jax.random.normal(k(13), (96, 64))
    got = tuned_call("gcn_agg", adj, feats, hw=HW, cache=cache,
                     interpret=True)
    np.testing.assert_allclose(got, ref.gcn_aggregate(adj, feats),
                               rtol=1e-4, atol=1e-4)

    qs = jax.random.normal(k(14), (60, 16))
    rs = jax.random.normal(k(15), (200, 16))
    gi, gd = tuned_call("nn_search", qs, rs, hw=HW, cache=cache,
                        interpret=True)
    wi, wd = ref.nn_search(qs, rs)
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))

    assert cache.stats.misses >= 9 and cache.stats.hits == 0


def test_ops_layer_routes_tuned_through_default_cache():
    cache = TuningCache(path=None)
    set_default_cache(cache)
    with ops.force("interpret"), ops.policy("tuned"):
        x = jnp.arange(4096, dtype=jnp.float32)
        ops.vecadd(x, x, hw=HW)
        assert cache.stats.misses == 1
        ops.vecadd(x, x, hw=HW)
        assert cache.stats.hits == 1


def test_ops_context_managers_restore_state():
    """The scoped forms never leak process-wide configuration — even when
    the body raises."""
    assert ops._DEFAULT_POLICY is MappingPolicy.AUTO and ops._FORCE == "auto"
    with ops.policy("tuned"), ops.force("ref"):
        assert ops._DEFAULT_POLICY is MappingPolicy.TUNED
        assert ops._FORCE == "ref"
    assert ops._DEFAULT_POLICY is MappingPolicy.AUTO and ops._FORCE == "auto"

    with pytest.raises(RuntimeError):
        with ops.policy("naive"), ops.measuring("cached"):
            raise RuntimeError("boom")
    assert ops._DEFAULT_POLICY is MappingPolicy.AUTO
    assert ops.get_default_measure() == "off"
