"""MoE layer: routing exactness, capacity drops, group-locality, EP
shardability of the dispatch; the dropless layer serving runs, whole and
as one chip's share of the experts."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.models.moe as moe_mod
from repro.configs import get_config
from repro.configs.base import ExpertShare
from repro.models.layers import NO_SHARD, ShardCtx, init_params
from repro.models.moe import moe_dropless, moe_mlp, moe_specs


def make(name="deepseek-moe-16b"):
    cfg = dataclasses.replace(get_config(name).reduced(), dtype="float32")
    specs = moe_specs(cfg)
    params = init_params(specs, jax.random.key(0), jnp.float32)
    return cfg, params


def dense_reference(params, h, cfg):
    """Route every token to its top-k experts WITHOUT capacity limits."""
    b, s, d = h.shape
    x = h.reshape(-1, d)
    logits = x @ params["router"]
    probs = jax.nn.softmax(logits, -1)
    gates, eidx = jax.lax.top_k(probs, cfg.moe_topk)
    gates = gates / gates.sum(-1, keepdims=True)
    act = jax.nn.silu if cfg.mlp_act == "swiglu" else jax.nn.gelu
    # compute every expert densely, gather
    g = jnp.einsum("td,edf->tef", x, params["w_gate"])
    u = jnp.einsum("td,edf->tef", x, params["w_up"])
    all_out = jnp.einsum("tef,efd->ted", act(g) * u, params["w_down"])
    y = jnp.einsum("tk,tkd->td", gates,
                   jnp.take_along_axis(all_out, eidx[..., None], axis=1))
    if "shared" in params:
        sp = params["shared"]
        y = y + (act(x @ sp["w_gate"]) * (x @ sp["w_up"])) @ sp["w_down"]
    return y.reshape(b, s, d)


def test_no_drop_equals_dense_reference():
    cfg, params = make()
    h = jax.random.normal(jax.random.key(1), (2, 16, cfg.d_model)) * 0.5
    out, aux = moe_mlp(params, h, cfg, NO_SHARD, capacity=32 * cfg.moe_topk)
    want = dense_reference(params, h, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    assert float(aux) > 0.9        # load-balance loss near 1 at init


def test_groups_equal_single_group_when_capacity_ample():
    cfg, params = make()
    h = jax.random.normal(jax.random.key(1), (4, 8, cfg.d_model)) * 0.5
    out1, _ = moe_mlp(params, h, cfg, NO_SHARD, capacity=1024)
    ctx4 = ShardCtx(flags={"moe_groups": 4})
    out4, _ = moe_mlp(params, h, cfg, ctx4, capacity=1024)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out4),
                               rtol=1e-4, atol=1e-4)


def test_capacity_drops_are_bounded():
    """with tight capacity the output differs but stays finite, and the
    per-token deviation is bounded by the dropped gate mass."""
    cfg, params = make()
    h = jax.random.normal(jax.random.key(1), (2, 32, cfg.d_model)) * 0.5
    full, _ = moe_mlp(params, h, cfg, NO_SHARD, capacity=64 * cfg.moe_topk)
    tight, _ = moe_mlp(params, h, cfg, NO_SHARD, capacity=8)
    assert bool(jnp.isfinite(tight).all())
    assert not np.allclose(np.asarray(full), np.asarray(tight))


def test_gradients_flow_through_dispatch():
    cfg, params = make()
    h = jax.random.normal(jax.random.key(1), (2, 16, cfg.d_model)) * 0.5

    def loss(p):
        out, aux = moe_mlp(p, h, cfg, NO_SHARD)
        return (out ** 2).sum() + 0.01 * aux

    grads = jax.grad(loss)(params)
    gnorms = {k: float(jnp.abs(v).sum()) for k, v in
              jax.tree_util.tree_flatten_with_path(grads)[0] and
              {jax.tree_util.keystr(p): jnp.abs(l).sum()
               for p, l in jax.tree_util.tree_leaves_with_path(grads)}.items()}
    # every expert weight and the router must receive gradient
    assert gnorms["['router']"] > 0
    assert gnorms["['w_gate']"] > 0 and gnorms["['w_down']"] > 0


def test_qwen3_moe_reduced_smoke():
    cfg, params = make("qwen3-moe-235b-a22b")
    h = jax.random.normal(jax.random.key(1), (2, 16, cfg.d_model)) * 0.5
    out, aux = moe_mlp(params, h, cfg, NO_SHARD)
    assert out.shape == h.shape and bool(jnp.isfinite(out).all())


# --------------------------------------------------------------------------- #
# The dropless layer serving runs
# --------------------------------------------------------------------------- #


def held_params(params, first, held):
    """The weights a share holds: the router whole, its experts' slice."""
    return dict(params, **{k: params[k][first:first + held]
                           for k in ("w_gate", "w_up", "w_down")})


@pytest.mark.parametrize("name", ["qwen3-moe-235b-a22b", "deepseek-moe-16b"])
def test_dropless_equals_uncut_reference(name):
    cfg, params = make(name)
    h = jax.random.normal(jax.random.key(1), (2, 16, cfg.d_model)) * 0.5
    out, load = moe_dropless(params, h, cfg)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(dense_reference(params, h, cfg)),
                               rtol=1e-5, atol=1e-5)
    assert load.shape == (cfg.moe_experts,)
    assert int(load.sum()) == 2 * 16 * cfg.moe_topk


def test_dropless_one_expert_takes_the_whole_batch():
    """Every token routed to expert 5: no pair is dropped, where the
    capacity layer must drop most of them."""
    cfg, params = make("qwen3-moe-235b-a22b")
    params = dict(params, router=jnp.zeros_like(params["router"])
                  .at[:, 5].set(1.0))
    h = jnp.abs(jax.random.normal(jax.random.key(2),
                                  (2, 16, cfg.d_model))) + 0.1
    want = dense_reference(params, h, cfg)
    out, load = moe_dropless(params, h, cfg)
    assert int(load[5]) == 32
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    capped, _ = moe_mlp(params, h, cfg, NO_SHARD)
    assert not np.allclose(np.asarray(capped), np.asarray(want), atol=1e-3)


def test_expert_shares_add_up_to_the_uncut_layer():
    """4 shares of a 16-expert top-4 layer, each computing only its own
    experts' part, add up to the uncut layer; every pair lands on
    exactly one share."""
    cfg = dataclasses.replace(make()[0], moe_experts=16, moe_topk=4,
                              moe_shared_experts=0)
    params = init_params(moe_specs(cfg), jax.random.key(3), jnp.float32)
    h = jax.random.normal(jax.random.key(4), (3, 8, cfg.d_model)) * 0.5
    total, pairs = 0.0, 0
    for rank in range(4):
        part = ExpertShare.of(cfg, 4 * rank, 4)
        specs = moe_specs(part)
        assert specs["router"].shape == (cfg.d_model, 16)
        assert specs["w_gate"].shape == (4, cfg.d_model, cfg.moe_dff)
        out, load = moe_dropless(held_params(params, 4 * rank, 4), h, part)
        total, pairs = total + out, pairs + int(load.sum())
    np.testing.assert_allclose(np.asarray(total),
                               np.asarray(dense_reference(params, h, cfg)),
                               rtol=1e-5, atol=1e-5)
    assert pairs == 3 * 8 * 4


def test_rows_not_in_flight_add_no_expert_work():
    cfg, params = make("qwen3-moe-235b-a22b")
    h = jax.random.normal(jax.random.key(5), (6, 1, cfg.d_model)) * 0.5
    rows = jnp.asarray([True, False, True, False, False, True])
    full, full_load = moe_dropless(params, h, cfg)
    out, load = moe_dropless(params, h, cfg, rows)
    np.testing.assert_array_equal(np.asarray(out[~rows]), 0.0)
    np.testing.assert_allclose(np.asarray(out[rows]),
                               np.asarray(full[rows]), rtol=1e-6, atol=1e-6)
    _, live_load = moe_dropless(params, h[rows], cfg)
    np.testing.assert_array_equal(np.asarray(load), np.asarray(live_load))
    assert int(full_load.sum()) == 6 * cfg.moe_topk


def test_a_share_must_lie_inside_the_router():
    cfg = make()[0]
    with pytest.raises(ValueError, match="not a share"):
        ExpertShare.of(cfg, 6, 4)
    with pytest.raises(ValueError, match="every expert held"):
        moe_mlp(held_params(make()[1], 0, 4),
                jnp.zeros((1, 2, cfg.d_model)), ExpertShare.of(cfg, 0, 4),
                NO_SHARD)
