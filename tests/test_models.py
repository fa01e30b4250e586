"""Per-architecture smoke tests (assignment requirement): a REDUCED config
of each family runs one forward/train step on CPU with correct shapes and
no NaNs; decode paths agree with the teacher-forced forward."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, list_configs
from repro.models import build_model

ALL_ARCHS = list_configs()


def reduced_f32(name):
    return dataclasses.replace(get_config(name).reduced(), dtype="float32")


def batch_for(m, b=2, s=24, key=1):
    cfg = m.cfg
    d = {}
    text = s
    if cfg.family == "vlm":
        text = s - cfg.prefix_tokens
        d["patches"] = jnp.full((b, cfg.prefix_tokens, cfg.d_model), 0.01,
                                m.dtype)
    if cfg.family == "encdec":
        d["frames"] = jnp.full((b, cfg.encoder_tokens, cfg.d_model), 0.01,
                               m.dtype)
    d["tokens"] = jax.random.randint(jax.random.key(key), (b, text), 0,
                                     cfg.vocab_size)
    d["labels"] = jnp.roll(d["tokens"], -1, 1)
    d["mask"] = jnp.ones((b, text), jnp.float32)
    return d, text


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_forward_and_grads(arch):
    """One forward + one grad step: output shapes, finite values."""
    cfg = reduced_f32(arch)
    m = build_model(cfg)
    params = m.init(jax.random.key(0))
    batch, text = batch_for(m)
    logits, aux = m.forward(params, batch)[:2]
    expect_s = text + (cfg.prefix_tokens if cfg.family == "vlm" else 0)
    assert logits.shape == (2, expect_s, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all()), arch

    (loss, metrics), grads = jax.value_and_grad(
        lambda p: m.loss(p, batch), has_aux=True)(params)
    assert bool(jnp.isfinite(loss))
    for leaf in jax.tree.leaves(grads):
        assert bool(jnp.isfinite(leaf).all()), arch


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_decode_step(arch):
    """init_cache + one decode step: shapes + finiteness."""
    cfg = reduced_f32(arch)
    m = build_model(cfg)
    params = m.init(jax.random.key(0))
    cache = m.init_cache(batch=2, max_len=16)
    tok = jnp.ones((2, 1), jnp.int32)
    # (moe also returns the expert layer's load)
    logits, cache2 = m.decode_step(params, cache, tok)[:2]
    lg = logits[:, 0] if logits.ndim == 3 else logits
    assert lg.shape == (2, cfg.vocab_size)
    assert bool(jnp.isfinite(lg).all())
    assert int(cache2["pos"]) == 1


@pytest.mark.parametrize("arch", ["smollm-135m", "gemma3-27b", "qwen3-8b",
                                  "mamba2-1.3b", "whisper-medium",
                                  "paligemma-3b", "nemotron-4-340b",
                                  "qwen3-moe-235b-a22b"])
def test_prefill_decode_matches_forward(arch):
    """prefill(t[:n-1]) + decode(t[n-1]) == forward(t)[-1].

    MoE archs use relaxed tolerance: capacity-based routing drops differ
    between the two paths by construction (verified exact when capacity
    covers all slots in test_moe.py)."""
    cfg = reduced_f32(arch)
    m = build_model(cfg)
    params = m.init(jax.random.key(0))
    b, s = 2, 20
    batch, text = batch_for(m, b, s)
    toks = batch["tokens"]
    full = m.forward(params, batch)[0]
    pbatch = dict(batch, tokens=toks[:, :-1])
    _, cache = m.prefill(params, pbatch, max_len=32)
    lg = m.decode_step(params, cache, toks[:, -1:])[0]
    got = lg[:, 0] if lg.ndim == 3 else lg
    atol = 0.2 if cfg.family == "moe" else 1e-3
    np.testing.assert_allclose(np.asarray(got), np.asarray(full[:, -1]),
                               atol=atol, rtol=0.1 if cfg.family == "moe"
                               else 1e-3)


def test_hybrid_step_decode_matches_forward():
    """zamba2: decoding token-by-token from scratch equals forward."""
    cfg = reduced_f32("zamba2-7b")
    m = build_model(cfg)
    params = m.init(jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (2, 8), 0, cfg.vocab_size)
    full = m.forward(params, {"tokens": toks})[0]
    cache = m.init_cache(2, 12)
    outs = []
    for t in range(8):
        lg, cache = m.decode_step(params, cache, toks[:, t:t + 1])
        outs.append(lg[:, 0] if lg.ndim == 3 else lg)
    np.testing.assert_allclose(np.asarray(jnp.stack(outs, 1)),
                               np.asarray(full), atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_param_count_matches_analytic(arch):
    """spec-tree parameter count ~= the analytic n_params() formula."""
    cfg = get_config(arch)
    m = build_model(cfg)
    analytic = cfg.n_params()
    actual = m.param_count()
    assert abs(actual - analytic) / analytic < 0.05, \
        (arch, actual / 1e9, analytic / 1e9)


def test_gemma3_local_global_flags():
    from repro.models.transformer import layer_flags
    cfg = get_config("gemma3-27b")
    flags = np.asarray(layer_flags(cfg))
    # 5 local then 1 global, repeating
    assert not flags[:5].any() and flags[5]
    assert flags.sum() == len(flags) // 6 + (1 if len(flags) % 6 == 0 else 0)


def test_vlm_prefix_attention_is_bidirectional():
    """a prefix patch change must affect EARLIER prefix positions' output
    (prefix-LM), but a suffix token change must not affect the prefix."""
    cfg = reduced_f32("paligemma-3b")
    m = build_model(cfg)
    params = m.init(jax.random.key(0))
    batch, text = batch_for(m, b=1, s=16)
    lg1 = m.forward(params, batch)[0]
    # perturb LAST patch -> first-position logits must change
    p2 = batch["patches"].at[:, -1].add(1.0)
    lg2 = m.forward(params, dict(batch, patches=p2))[0]
    assert not np.allclose(np.asarray(lg1[:, 0]), np.asarray(lg2[:, 0]))
    # perturb last TEXT token -> prefix logits unchanged (causality)
    t2 = batch["tokens"].at[:, -1].set((batch["tokens"][:, -1] + 1)
                                       % cfg.vocab_size)
    lg3 = m.forward(params, dict(batch, tokens=t2))[0]
    np.testing.assert_allclose(np.asarray(lg1[:, :cfg.prefix_tokens]),
                               np.asarray(lg3[:, :cfg.prefix_tokens]),
                               atol=1e-5)
