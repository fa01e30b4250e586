"""FLOP and byte counts against values worked out by hand at both
configurations' shapes, as the dense family file counts them."""

import json

import pytest

from harness import spec, work
from harness.spec import BENCH

DENSE = spec.family({"name": "qwen3-8b-9l", "family": "dense"})


def shapes(name):
    conf = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    return spec.family(conf).shapes(conf)


#: Qwen3-8B's published widths (hf:Qwen/Qwen3-8B) at 9 of its 36 layers
QWEN3_9L = DENSE.Shapes(layers=9, d=4096, heads=32, kv_heads=8, head_dim=128,
                        d_ff=12288, vocab=151936)


def test_smollm_counts_by_hand():
    sh = shapes("smollm-135m")
    # q 576x9x64 + k,v 2x576x3x64 + o 9x64x576 + mlp 3x576x1536
    per_layer = 331776 + 221184 + 331776 + 2654208
    assert sh.layer_matmul_params == per_layer == 3538944
    assert sh.token_flops == 2 * 30 * 3538944 == 212336640
    assert sh.head_flops == 2 * 576 * 49152 == 56623104
    # one query against 100 keys: 4 * 9 heads * 64 * 100 per layer
    assert sh.attn_flops(100) == 30 * 4 * 9 * 64 * 100 == 6912000


def test_qwen3_9l_counts_by_hand():
    sh = QWEN3_9L
    # q 4096x32x128 + k,v 2x4096x8x128 + o 32x128x4096 + mlp 3x4096x12288
    per_layer = 16777216 + 8388608 + 16777216 + 150994944
    assert sh.layer_matmul_params == per_layer == 192937984
    assert sh.token_flops == 2 * 9 * 192937984
    assert sh.head_flops == 2 * 4096 * 151936


def test_causal_keys():
    assert work.causal_keys(0, 4) == 1 + 2 + 3 + 4
    assert work.causal_keys(10, 3) == 11 + 12 + 13
    # chunks add up to the whole prompt
    assert (work.causal_keys(0, 128) + work.causal_keys(128, 72)
            == work.causal_keys(0, 200))


def test_prefill_chunk_flops_qwen():
    sh = QWEN3_9L
    f = work.prefill_chunk_flops(sh, 256, 128, last=True)
    keys = 128 * 256 + 128 * 129 // 2
    assert f == (128 * 2 * 9 * 192937984 + 4 * 9 * 32 * 128 * keys
                 + 2 * 4096 * 151936)


def test_flash_chunk_work_smollm():
    sh = shapes("smollm-135m")
    flops, nbytes = work.flash_chunk_work(sh, 64, 64)
    assert flops == 4 * 30 * 9 * 64 * (64 * 64 + 64 * 65 // 2)
    # per layer: q and o (64 x 9 x 64 each) and k, v up to 128 (128 x 3 x 64
    # each), 2 bytes
    assert nbytes == 30 * 2 * (2 * 64 * 9 * 64 + 2 * 128 * 3 * 64)


def test_paged_decode_work_qwen():
    sh = QWEN3_9L
    flops, nbytes = work.paged_decode_work(sh, [1000, 24])
    assert flops == 4 * 9 * 32 * 128 * 1024
    assert nbytes == 9 * (2 * 2 * 1024 * 8 * 128 + 2 * 2 * 2 * 32 * 128)


def test_decode_tick_flops():
    sh = shapes("smollm-135m")
    assert work.decode_tick_flops(sh, [10, 20]) == (
        2 * (sh.token_flops + sh.head_flops) + sh.attn_flops(30))


def test_roofline_bound():
    assert work.roofline_seconds(197e12, 1.0, 197e12, 819e9) == (
        pytest.approx(1.0), "compute")
    assert work.roofline_seconds(1.0, 819e9, 197e12, 819e9) == (
        pytest.approx(1.0), "memory")
