"""Eq. 1 + block/mesh planner: unit + hypothesis property tests."""

import pytest
pytest.importorskip("hypothesis")
import hypothesis.strategies as st
from hypothesis import given, settings

from repro.core.hw import TPU_REGISTRY, VortexParams, ceil_div, detect
from repro.core import workload as W
from repro.core.mapper import (MappingPolicy, Regime, classify_regime,
                               plan_attention_blocks, plan_matmul_blocks,
                               plan_microbatch, plan_moe_capacity,
                               plan_vector_blocks, resolve_lws)

HW = TPU_REGISTRY["tpu_v5e"]


class TestEq1:
    def test_paper_example(self):
        # paper Fig.1: gws=128, hp=8 -> lws=16
        assert resolve_lws(128, 8) == 16

    def test_hp_exceeds_gws_resolves_to_1(self):
        # paper §3: "when hp exceeds the gws ... Eq. 1 resolves to lws=1"
        assert resolve_lws(100, 1024) == 1

    def test_regimes(self):
        assert classify_regime(1, 128, 8) is Regime.OVERSUBSCRIBED
        assert classify_regime(16, 128, 8) is Regime.EXACT
        assert classify_regime(64, 128, 8) is Regime.UNDERSUBSCRIBED

    @given(gws=st.integers(1, 1 << 22), hp=st.integers(1, 1 << 16))
    @settings(max_examples=200, deadline=None)
    def test_lws_covers_gws_without_waste(self, gws, hp):
        lws = resolve_lws(gws, hp)
        # coverage: lws * hp lanes can absorb all of gws in one call
        assert lws * hp >= gws
        # minimality: one less iteration per lane would need another call
        assert (lws - 1) * hp < gws or lws == 1


class TestVectorBlocks:
    @given(n=st.integers(1, 1 << 22),
           pol=st.sampled_from(list(MappingPolicy)))
    @settings(max_examples=100, deadline=None)
    def test_plan_invariants(self, n, pol):
        plan = plan_vector_blocks(W.vecadd(n), HW, pol)
        assert plan.block_elems >= 1
        assert plan.grid * plan.block_elems == plan.padded_gws >= n
        assert plan.vmem_bytes <= HW.vmem_budget_bytes or \
            plan.block_elems == HW.lane_parallelism
        assert 0 < plan.utilization <= 1.0

    def test_auto_beats_naive_grid(self):
        plan_a = plan_vector_blocks(W.vecadd(1 << 20), HW, MappingPolicy.AUTO)
        plan_n = plan_vector_blocks(W.vecadd(1 << 20), HW, MappingPolicy.NAIVE)
        assert plan_a.sequential_rounds <= plan_n.sequential_rounds


class TestMatmulBlocks:
    @given(m=st.integers(8, 8192), n=st.integers(8, 8192),
           k=st.integers(8, 8192), pol=st.sampled_from(list(MappingPolicy)))
    @settings(max_examples=100, deadline=None)
    def test_tiles_cover_and_fit(self, m, n, k, pol):
        p = plan_matmul_blocks(m, n, k, HW, pol)
        assert p.grid[0] * p.bm >= m and p.grid[1] * p.bn >= n
        assert p.grid[2] * p.bk >= k
        if pol is MappingPolicy.AUTO:
            assert p.vmem_bytes <= HW.vmem_budget_bytes
            assert p.bm % 8 == 0 and p.bn % 8 == 0

    def test_mxu_alignment(self):
        p = plan_matmul_blocks(4096, 4096, 4096, HW)
        assert p.bm % 128 == 0 and p.bn % 128 == 0 and p.bk % 128 == 0


class TestAttentionBlocks:
    @given(sq=st.integers(1, 1 << 16), skv=st.integers(128, 1 << 16))
    @settings(max_examples=60, deadline=None)
    def test_vmem_clamp(self, sq, skv):
        p = plan_attention_blocks(sq, skv, 128, HW)
        assert p.block_q >= 8 and p.block_k >= 128
        assert p.vmem_bytes <= HW.vmem_budget_bytes or \
            (p.block_q <= 128 and p.block_k <= 128)


class TestMeshPlan:
    @given(gb=st.integers(1, 4096), dp=st.sampled_from([1, 2, 8, 16, 32]),
           act=st.floats(1e6, 1e10), budget=st.floats(1e9, 2e10))
    @settings(max_examples=100, deadline=None)
    def test_microbatch_divides(self, gb, dp, act, budget):
        p = plan_microbatch(gb, dp, act, budget)
        assert p.per_device_batch * dp >= gb
        assert p.per_device_batch % p.num_microbatches == 0
        assert p.microbatch_per_device * p.num_microbatches \
            == p.per_device_batch

    def test_memory_regime_forces_accumulation(self):
        # activations 10x the budget -> must microbatch (paper's
        # "multiple kernel calls" regime, used productively)
        p = plan_microbatch(256, 16, 1e9, 4e9)
        assert p.num_microbatches >= 4
        assert p.regime is Regime.OVERSUBSCRIBED


class TestMoECapacity:
    @given(t=st.integers(1, 1 << 20), e=st.sampled_from([8, 64, 128]),
           k=st.integers(1, 8))
    @settings(max_examples=60, deadline=None)
    def test_capacity_covers_ideal(self, t, e, k):
        cap = plan_moe_capacity(t, e, k, ep_size=1)
        assert cap * e >= t * k          # slots cover all routed tokens
        assert cap % 8 == 0


class _Dev:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


def test_detect_keys_tpus_by_device_kind(monkeypatch):
    """A v5e is recognised by its ``device_kind``; a TPU kind with no
    registry entry is an error, not a v5e guess; a CPU gets the
    ``cpu_sim`` stand-in."""
    import jax

    monkeypatch.setattr(jax, "devices",
                        lambda: [_Dev("tpu", "TPU v5 lite")] * 4)
    assert detect() == TPU_REGISTRY["tpu_v5e"].with_chips(4)
    monkeypatch.setattr(jax, "devices", lambda: [_Dev("tpu", "TPU v4")])
    assert detect() == TPU_REGISTRY["tpu_v4"]
    monkeypatch.setattr(jax, "devices", lambda: [_Dev("tpu", "TPU v6 lite")])
    with pytest.raises(ValueError, match="unknown TPU"):
        detect()
    monkeypatch.setattr(jax, "devices", lambda: [_Dev("cpu", "cpu")])
    assert detect() == TPU_REGISTRY["cpu_sim"]
