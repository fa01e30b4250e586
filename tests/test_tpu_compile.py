"""Ahead-of-time compiles of the serving path's Pallas kernels for a
described TPU v5e chip (no chip attached), at smollm-135m widths, and of
the dropless expert layer at Qwen3-235B-A22B widths.

Interpret-mode tests cannot see what the TPU compiler refuses: a block
shape that breaks the (8, 128) tiling rule, or more VMEM than a kernel
may claim.  Each test here compiles one main-path kernel for the chip as
the models call it and checks that the kernel is in the compiled program
(``tpu_custom_call``).  The topology is described inside a fixture, never
at import: only one process at a time may load the TPU library.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.hw import TPU_REGISTRY
from repro.core.mapper import MappingPolicy

#: smollm-135m serving widths: 8 slots, a 512-token pool, 3 KV heads of
#: 3 query heads each, head_dim 64, 16-token pages, bf16
B, T, G, R, D, PB = 8, 512, 3, 3, 64, 16
V5E = TPU_REGISTRY["tpu_v5e"]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else the compiler logs to /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:   # no TPU compiler installed here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_v5e(monkeypatch):
    """The model-level kernel wrappers ask ``detect()``, which sees this
    host's CPU; hand them the v5e parameters the chip would report."""
    import repro.core.hw as hw

    monkeypatch.setattr(hw, "detect", lambda num_chips=None: V5E)


def _router(slots, pool):
    from repro.configs import get_config
    from repro.serve.buckets import BucketRouter, BucketSpec
    from repro.tuner import TuningCache

    return BucketRouter(get_config("smollm-135m"),
                        BucketSpec(max_len=pool, min_len=32), slots=slots,
                        hw=V5E, policy=MappingPolicy.TUNED,
                        cache=TuningCache(path=None), page_block=PB)


@pytest.fixture(scope="module")
def router():
    return _router(B, T)


def _assert_kernel_compiles(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text


#: (slots, pool) of the tests' serving widths and of the chat cell
CHAT = (64, 2048)


@pytest.mark.parametrize("kv,geometry", [
    ("bf16", (B, T)), ("int8", (B, T)), ("bf16", CHAT), ("int8", CHAT)],
    ids=["bf16", "int8", "bf16-chat", "int8-chat"])
def test_fused_paged_decode_compiles(one_chip, kv, geometry):
    """At the tests' widths, and at the chat cell's 64 slots over a
    2048-position pool with ``block_s`` as the v5e router resolves it
    there (the whole pool: one grid step per row, long rows streamed in
    sub-blocks)."""
    from repro.kernels.paged_decode_attention import \
        paged_decode_attention_pallas

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    b, t = geometry
    router = _router(b, t)
    block_s = router.resolve(router.bucket(t)).paged_decode_block
    cdt = jnp.bfloat16 if kv == "bf16" else jnp.int8
    args = [s((b, G, R, D), jnp.bfloat16), s((b, t, G, D), cdt),
            s((b, t, G, D), cdt), s((b, t // PB), jnp.int32),
            s((b,), jnp.int32)]
    if kv == "int8":
        args += [s((b, t // PB, G), jnp.float32)] * 2

    def fn(q, k, v, tbl, clen, *scales):
        ks, vs = scales if scales else (None, None)
        return paged_decode_attention_pallas(
            q, k, v, tbl, clen, page_block=PB, block_s=block_s,
            k_scale=ks, v_scale=vs)

    _assert_kernel_compiles(fn, *args)


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_paged_gather_ablation_compiles(one_chip, kv):
    from repro.kernels.paged_gather import (paged_dequant_gather_pallas,
                                            paged_gather_pallas)

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    tbl = s((B, T // PB), jnp.int32)
    if kv == "bf16":
        _assert_kernel_compiles(lambda c, t: paged_gather_pallas(c, t, PB),
                                s((B, T, G, D), jnp.bfloat16), tbl)
    else:
        _assert_kernel_compiles(
            lambda c, sc, t: paged_dequant_gather_pallas(
                c, sc, t, PB, out_dtype=jnp.bfloat16),
            s((B, T, G, D), jnp.int8), s((B, T // PB, G), jnp.float32), tbl)


@pytest.mark.parametrize("chunked", [False, True])
def test_flash_prefill_compiles_at_router_tiles(one_chip, router, on_v5e,
                                                chunked):
    """Whole-prompt prefill, and one chunk of chunked prefill (the chunk's
    queries at a traced offset into the prompt bucket's cache)."""
    from repro.models.attention import pallas_prefill_attention

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pb = 256
    bq, bk = router.prefill_tiles(pb)
    sq = bq if chunked else pb
    kv = s((1, pb, G, D), jnp.bfloat16)
    q = s((1, sq, G, R, D), jnp.bfloat16)
    if chunked:
        _assert_kernel_compiles(
            lambda q, k, v, off: pallas_prefill_attention(
                q, k, v, block_q=bq, block_k=bk, q_offset=off),
            q, kv, kv, s((), jnp.int32))
    else:
        _assert_kernel_compiles(
            lambda q, k, v: pallas_prefill_attention(
                q, k, v, block_q=bq, block_k=bk), q, kv, kv)


def test_dense_decode_attention_compiles(one_chip, router, on_v5e):
    """``decode_attention_pallas`` vmapped over batch and heads, as the
    unpaged pool and the gather ablation call it."""
    from repro.models.attention import pallas_decode_attention

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    block = router.resolve(router.bucket(T)).decode_block
    cache = s((B, T, G, D), jnp.bfloat16)
    _assert_kernel_compiles(
        lambda q, k, v, c: pallas_decode_attention(q, k, v, c, block=block),
        s((B, G, R, D), jnp.bfloat16), cache, cache, s((B,), jnp.int32))


def test_matmul_auto_compiles_at_4096(one_chip):
    """Eq. 1's AUTO plan at 4096² bf16 fits the VMEM limit it declares."""
    from repro.kernels.matmul import matmul_pallas

    a = jax.ShapeDtypeStruct((4096, 4096), jnp.bfloat16, sharding=one_chip)
    _assert_kernel_compiles(
        lambda x, y: matmul_pallas(x, y, hw=V5E, policy=MappingPolicy.AUTO),
        a, a)


@pytest.mark.parametrize("tokens", [64, 1024], ids=["decode-64", "chunk-1024"])
def test_dropless_expert_layer_compiles_at_qwen3_moe_widths(one_chip, tokens):
    """The serving expert layer of the Qwen3-235B-A22B cell (d 4096, a
    router of 128, top 8, experts 0-7 held, width 1536) over the decode
    step's 64 rows and a 1024-token prefill chunk: its grouped matmuls
    run as XLA's TPU kernel (``%ragged-dot-none``), not a dense loop."""
    from repro.configs import get_config
    from repro.configs.base import ExpertShare
    from repro.models.moe import moe_dropless

    cfg = ExpertShare.of(get_config("qwen3-moe-235b-a22b"), 0, 8)
    d, ff = cfg.d_model, cfg.moe_dff

    def s(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    params = {"router": s((d, 128)), "w_gate": s((8, d, ff)),
              "w_up": s((8, d, ff)), "w_down": s((8, ff, d))}
    text = jax.jit(lambda p, h, v: moe_dropless(p, h, cfg, v)).lower(
        params, s((tokens, 1, d)), s((tokens, 1), jnp.bool_)
    ).compile().as_text()
    assert "tpu_custom_call" in text and "%ragged-dot-none" in text
