"""§Perf levers: banded attention, int8 KV cache, fp8 a2a, moe remat,
serve-mesh chooser — correctness of each beyond-paper optimization."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.models import build_model
from repro.models import transformer as tf
from repro.models.attention import banded_attention, chunked_attention
from repro.models.layers import ShardCtx


class TestBandedAttention:
    @pytest.mark.parametrize("window,band", [(32, 32), (32, 64), (64, 64)])
    def test_matches_masked_full_sweep(self, window, band):
        B, S, G, R, D = 2, 256, 2, 2, 16
        q = jax.random.normal(jax.random.key(0), (B, S, G, R, D)) * 0.5
        k = jax.random.normal(jax.random.key(1), (B, S, G, D)) * 0.5
        v = jax.random.normal(jax.random.key(2), (B, S, G, D)) * 0.5
        ref = chunked_attention(q, k, v, causal=True, window=window,
                                chunk=64)
        got = banded_attention(q, k, v, window=window, band=band)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-5)

    def test_gradients_match(self):
        B, S, G, R, D = 1, 128, 1, 2, 8
        q = jax.random.normal(jax.random.key(0), (B, S, G, R, D)) * 0.5
        k = jax.random.normal(jax.random.key(1), (B, S, G, D)) * 0.5
        v = jax.random.normal(jax.random.key(2), (B, S, G, D)) * 0.5
        g1 = jax.grad(lambda q, k, v: (banded_attention(
            q, k, v, window=32, band=32) ** 2).sum(), (0, 1, 2))(q, k, v)
        g2 = jax.grad(lambda q, k, v: (chunked_attention(
            q, k, v, causal=True, window=32, chunk=32) ** 2).sum(),
            (0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-3)


class TestBandedGemmaForward:
    def test_grouped_forward_exact(self):
        cfg = ModelConfig(name="t", family="dense", num_layers=9,
                          d_model=64, num_heads=4, num_kv_heads=2,
                          d_ff=128, vocab_size=256, head_dim=16, window=32,
                          local_global_ratio=3, dtype="float32")
        m = build_model(cfg)
        params = m.init(jax.random.key(0))
        toks = jax.random.randint(jax.random.key(1), (2, 128), 0, 256)
        ref, _, (rk, rv) = tf.forward(params, toks, cfg, return_cache=True)
        ctx = ShardCtx(flags={"banded_local": True})
        got, _, (gk, gv) = tf.forward(params, toks, cfg, ctx=ctx,
                                      return_cache=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-3)
        np.testing.assert_allclose(np.asarray(gk), np.asarray(rk),
                                   atol=1e-5)


class TestInt8KVCache:
    def test_decode_close_to_bf16(self):
        cfg = dataclasses.replace(get_config("qwen3-8b").reduced(),
                                  dtype="float32")
        m = build_model(cfg)
        params = m.init(jax.random.key(0))
        toks = jax.random.randint(jax.random.key(1), (2, 8), 0,
                                  cfg.vocab_size)
        c_f = m.init_cache(2, 12)
        c_q = m.init_cache(2, 12, cache_dtype="int8")
        assert c_q["k"].dtype == jnp.int8
        for t in range(8):
            lf, c_f = m.decode_step(params, c_f, toks[:, t:t + 1])
            lq, c_q = m.decode_step(params, c_q, toks[:, t:t + 1])
        rel = float(jnp.abs(lf - lq).max() / jnp.abs(lf).max())
        # bound is jaxlib-sensitive: matmul accumulation order shifts the
        # quantization-noise peak, so it sits above the typical error
        assert rel < 0.08, rel


class TestFP8A2A:
    def test_moe_forward_close(self):
        cfg = dataclasses.replace(get_config("deepseek-moe-16b").reduced(),
                                  dtype="float32")
        m = build_model(cfg)
        params = m.init(jax.random.key(0))
        toks = jax.random.randint(jax.random.key(1), (2, 16), 0,
                                  cfg.vocab_size)
        ref, _ = tf.forward(params, toks, cfg)
        ctx = ShardCtx(flags={"moe_fp8_a2a": True})
        got, _ = tf.forward(params, toks, cfg, ctx=ctx)
        rel = float(jnp.abs(ref - got).max() / jnp.abs(ref).max())
        assert rel < 0.15, rel          # fp8 e4m3, scale folded (doc'd)

    def test_moe_remat_policy_grads(self):
        cfg = dataclasses.replace(get_config("deepseek-moe-16b").reduced(),
                                  dtype="float32")
        m = build_model(cfg)
        params = m.init(jax.random.key(0))
        toks = jax.random.randint(jax.random.key(1), (2, 16), 0,
                                  cfg.vocab_size)
        g = jax.grad(lambda p: (tf.forward(p, toks, cfg, remat="moe")[0]
                                .astype(jnp.float32) ** 2).mean())(params)
        for leaf in jax.tree.leaves(g):
            assert bool(jnp.isfinite(leaf).all())


class TestServeMeshChooser:
    def test_nemotron_needs_tp64(self):
        from repro.runtime.sharding import choose_serve_mesh
        dp, tp = choose_serve_mesh(get_config("nemotron-4-340b"))
        assert tp == 64 and dp * tp == 256
        # weights now fit model-only
        n = get_config("nemotron-4-340b").n_params() * 2
        assert n / tp <= 12 * 1024**3

    def test_small_model_keeps_default(self):
        from repro.runtime.sharding import choose_serve_mesh
        dp, tp = choose_serve_mesh(get_config("qwen3-8b"))
        assert tp <= 4

    def test_decode_cache_seq_rule(self):
        """the mapper's Eq.1 cache decision (HC2 iteration 1)."""
        from repro.configs import SHAPES
        from tests.test_sharding import prod_plan
        _, plan = prod_plan("nemotron-4-340b", "decode_32k")
        assert plan.act_rules["cache_seq"] == "model"
        assert plan.kv_mode == "replicated"
        _, plan2 = prod_plan("gemma3-27b", "decode_32k")
        assert plan2.act_rules["cache_seq"] is None       # no win: kv%tp==0


class TestTriangularPrefill:
    def test_matches_flash(self):
        import jax.numpy as jnp
        from repro.models.attention import (chunked_attention,
                                            triangular_attention)
        B, S, G, R, D = 2, 256, 2, 2, 16
        q = jax.random.normal(jax.random.key(0), (B, S, G, R, D)) * 0.5
        k = jax.random.normal(jax.random.key(1), (B, S, G, D)) * 0.5
        v = jax.random.normal(jax.random.key(2), (B, S, G, D)) * 0.5
        ref = chunked_attention(q, k, v, causal=True, chunk=64)
        got = triangular_attention(q, k, v, chunk=64)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-5)

    def test_prefill_path_with_flag(self):
        cfg = dataclasses.replace(get_config("qwen3-8b").reduced(),
                                  dtype="float32")
        m = build_model(cfg)
        params = m.init(jax.random.key(0))
        toks = jax.random.randint(jax.random.key(1), (2, 32), 0,
                                  cfg.vocab_size)
        ref = m.forward(params, {"tokens": toks})[0]
        ctx = ShardCtx(flags={"triangular_causal": True})
        got = m.forward(params, {"tokens": toks}, ctx=ctx)[0]
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-3, rtol=1e-3)
