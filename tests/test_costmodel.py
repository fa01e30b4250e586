"""Cost model calibration + the two XLA-CPU artifacts it works around.

If either pinned artifact disappears in a future jax (loop-aware
cost_analysis / native-bf16 CPU buffers), these tests flag that the
dry-run should switch back to compiled numbers.
"""

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import ModelConfig, ShapeConfig
from repro.core import costmodel as cm
from repro.core.roofline import collective_stats_from_hlo
from repro.launch.mesh import make_local_mesh
from repro.launch.steps import (StepConfig, abstract_train_state,
                                make_decode_step, make_prefill_step,
                                make_train_step)
from repro.models import build_model
from repro.optim import AdamWConfig
from repro.runtime import sharding as shd

TINY = ModelConfig(name="tiny", family="dense", num_layers=1, d_model=256,
                   num_heads=4, num_kv_heads=2, d_ff=512, vocab_size=1024,
                   head_dim=64, dtype="float32")


def _flops(compiled) -> float:
    return float(compiled.cost_analysis()["flops"])


def test_xla_artifact_scan_flops_counted_once():
    """PINNED ASSUMPTION: cost_analysis does not multiply while-loop trip
    counts (this is why the roofline uses the analytic model)."""
    def one(a, b):
        return a @ b

    def scanned(a, b):
        c, _ = jax.lax.scan(lambda c, _: (c @ b, None), a, None, length=10)
        return c

    sh = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    f1 = _flops(jax.jit(one).lower(sh, sh).compile())
    f2 = _flops(jax.jit(scanned).lower(sh, sh).compile())
    assert f2 == pytest.approx(f1), \
        "cost_analysis became loop-aware — revisit core.costmodel usage"


@pytest.fixture(scope="module")
def tiny_setup():
    model = build_model(TINY)
    mesh = make_local_mesh(1, 1)
    shape = ShapeConfig("t", 512, 4, "train")
    plan = shd.resolve_plan(TINY, mesh, shape)
    return model, mesh, shape, plan


def test_train_flops_calibration(tiny_setup):
    """analytic flops within 15% of cost_analysis on a LOOP-FREE config
    (1 layer, 1 microbatch, seq == attention chunk)."""
    model, mesh, shape, plan = tiny_setup
    ts = make_train_step(model, AdamWConfig(), plan,
                         StepConfig(remat="none", microbatches=1))
    state = abstract_train_state(model, plan)
    batch = model.input_specs(shape)
    measured = _flops(jax.jit(ts).lower(state, batch).compile())
    analytic = cm.cell_cost(TINY, shape, plan, microbatches=1,
                            remat="none").flops
    assert 0.85 < analytic / measured < 1.25, (analytic, measured)


def test_prefill_flops_calibration(tiny_setup):
    model, mesh, shape, plan = tiny_setup
    sp = ShapeConfig("p", 512, 4, "prefill")
    pf = make_prefill_step(model, plan, max_len=512)
    params = model.abstract_params()
    measured = _flops(jax.jit(pf).lower(
        params, {"tokens": jax.ShapeDtypeStruct((4, 512), jnp.int32)}
    ).compile())
    analytic = cm.cell_cost(TINY, sp, plan).flops
    assert 0.85 < analytic / measured < 1.25


def test_decode_flops_calibration(tiny_setup):
    model, mesh, shape, plan = tiny_setup
    sd = ShapeConfig("d", 512, 4, "decode")
    dec = make_decode_step(model, plan)
    params = model.abstract_params()
    cache = model.init_cache(4, 512, abstract=True)
    measured = _flops(jax.jit(dec).lower(
        params, cache, jax.ShapeDtypeStruct((4, 1), jnp.int32)
    ).compile())
    analytic = cm.cell_cost(TINY, sd, plan).flops
    assert 0.85 < analytic / measured < 1.25


def test_memory_model_scales_with_microbatching():
    shape = ShapeConfig("t", 4096, 256, "train")
    cfg = TINY
    mesh = make_local_mesh(1, 1)
    plan = shd.resolve_plan(cfg, mesh, shape)
    c1 = cm.cell_cost(cfg, shape, plan, microbatches=1)
    c8 = cm.cell_cost(cfg, shape, plan, microbatches=8)
    assert c8.mem_bytes["remat_stash"] < c1.mem_bytes["remat_stash"]
    assert c8.flops == pytest.approx(c1.flops, rel=0.01)


def test_collective_parser():
    hlo = """
  %ag = bf16[16,1024]{1,0} all-gather(bf16[1,1024]{1,0} %x), replica_groups=[1,16]<=[16], dimensions={0}
  %ar = f32[256]{0} all-reduce(f32[256]{0} %y), replica_groups={{0,1,2,3}}, to_apply=%add
  %rs = bf16[2,64]{1,0} reduce-scatter(bf16[32,64]{1,0} %z), replica_groups=[1,16]<=[16], dimensions={0}
    """
    st = collective_stats_from_hlo(hlo, world=16)
    assert st.count_by_kind == {"all-gather": 1, "all-reduce": 1,
                                "reduce-scatter": 1}
    assert st.bytes_by_kind["all-gather"] == pytest.approx(
        15 / 16 * 16 * 1024 * 2)
    assert st.bytes_by_kind["all-reduce"] == pytest.approx(
        2 * 3 / 4 * 256 * 4)
    assert st.bytes_by_kind["reduce-scatter"] == pytest.approx(
        15 / 16 * 32 * 64 * 2)
