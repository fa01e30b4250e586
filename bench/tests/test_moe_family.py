"""The sparse-expert family (``bench/families/moe.py``): its reference
against a layer worked by hand and made to follow a given routing, its
weight fan-in over the expert axis, its work counts, and the new cell on
the CPU at the rehearsal widths: the float8 control fails the comparison
that decides ``correct``, and the program passes it but where a router
near-tie sent a token to other held experts."""

import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import spec, weights
from harness.spec import BENCH

CELL = "qwen3-moe-235b-a22b-8l.reasoning"
CONF = json.loads((BENCH / "configs" / "qwen3-moe-235b-a22b-8l.json")
                  .read_text())
moe = spec.family(CONF)

#: one layer, d 2, one head of 2, a router of 4 experts, top 2, of which
#: experts 2 and 3 are held here
HAND_SIZES = {"num_hidden_layers": 1, "hidden_size": 2,
              "num_attention_heads": 1, "num_key_value_heads": 1,
              "head_dim": 2, "rms_norm_eps": 1e-6, "rope_theta": 1e6,
              "num_experts_per_tok": 2, "first_expert": 2, "num_experts": 2,
              "tie_word_embeddings": False}


def _hand_params(expert2_scale=1.0):
    eye = np.eye(2, dtype=np.float32)
    L = lambda a: jnp.asarray(a, jnp.float32)[None]      # noqa: E731
    return {
        "embed": {"tok": jnp.asarray([[1.0, 1.0], [2.0, -1.0]]),
                  "unembed": jnp.asarray([[1.0, 0.0, 2.0, -1.0],
                                          [0.0, 1.0, 0.0, 1.0]])},
        "ln_f": jnp.zeros(2),
        "blocks": {
            "ln1": L(np.zeros(2)), "ln2": L(np.zeros(2)),
            "attn": {"wq": L(eye[:, None, :]), "wk": L(eye[:, None, :]),
                     "wv": L(eye[:, None, :]), "wo": L(0.5 * eye[None]),
                     "q_norm": L(np.zeros(2)), "k_norm": L(np.zeros(2))},
            # router logits of the normalised [1, 1]: [0, 2, 1, 3]
            "moe": {"router": L([[0.0, 1.0, 0.5, 1.5],
                                 [0.0, 1.0, 0.5, 1.5]]),
                    "w_gate": L([expert2_scale * eye, eye]),
                    "w_up": L([expert2_scale * eye, 2 * eye]),
                    "w_down": L([expert2_scale * eye,
                                 [[1.0, 0.0], [0.0, 0.0]]])}},
    }


def test_reference_against_a_layer_worked_by_hand():
    """Token 0 ([1, 1]) alone: attention returns its own value, so the
    residual is [1.5, 1.5]; the router picks experts 3 and 1, of which
    only 3 is held, with the renormalised gate e^3 / (e^3 + e^2)."""
    eps = HAND_SIZES["rms_norm_eps"]
    n1 = 1 / math.sqrt(1 + eps)                  # rms of [1, 1] is 1
    x = np.array([1.0, 1.0]) + 0.5 * n1          # + o(v(rms(x)))
    h = x / math.sqrt(np.mean(x * x) + eps)
    z = np.array([0.0, 2.0, 1.0, 3.0]) * h[0]   # router logits
    g3 = math.exp(z[3]) / (math.exp(z[3]) + math.exp(z[1]))
    silu = h / (1 + np.exp(-h))
    x = x + g3 * np.array([silu[0] * 2 * h[0], 0.0])
    xn = x / math.sqrt(np.mean(x * x) + eps)
    want = xn @ np.array([[1.0, 0.0, 2.0, -1.0], [0.0, 1.0, 0.0, 1.0]])

    got = moe.logits(_hand_params(), HAND_SIZES, [0], [0])
    np.testing.assert_allclose(got[0], want, rtol=1e-6, atol=1e-6)
    # expert 2 is held but not routed: its weights change nothing
    np.testing.assert_array_equal(
        moe.logits(_hand_params(expert2_scale=7.0), HAND_SIZES, [0], [0]),
        got)
    # held from expert 0 on, the share's two experts give other logits
    other = dict(HAND_SIZES, first_expert=0)
    assert not np.allclose(moe.logits(_hand_params(), other, [0], [0]), got)


def test_fan_in_over_the_expert_axis():
    assert moe.fan_in("w_gate", (8, 4096, 1536)) == 4096
    assert moe.fan_in("w_down", (8, 1536, 4096)) == 1536
    assert moe.fan_in("router", (4096, 128)) == 4096
    assert moe.fan_in("wo", (64, 128, 4096)) == 64 * 128
    assert moe.fan_in("wq", (4096, 64, 128)) == 4096

    def init(key):
        return {"blocks": {"moe": {"w_gate": jnp.zeros((2, 8, 64, 32)),
                                   "router": jnp.zeros((2, 64, 128))}}}

    w = weights.make_params(init, 2**33 + 7, moe.fan_in)["blocks"]["moe"]
    assert float(jnp.std(w["w_gate"])) == pytest.approx(64 ** -0.5,
                                                        rel=0.05)
    assert float(jnp.std(w["router"])) == pytest.approx(64 ** -0.5,
                                                        rel=0.05)


def test_work_counts_by_hand():
    sh = moe.shapes(spec.sizes(CONF))
    d, ff = 4096, 1536
    attn = d * 64 * 128 + 2 * d * 4 * 128 + 64 * 128 * d
    router = d * 128
    pairs = 8 * 8 * 3 * d * ff // 128          # top-8 x 8 held of 128
    assert sh.layer_matmul_params == attn + router + pairs
    assert sh.token_flops == 2 * 8 * (attn + router + pairs)
    assert sh.head_flops == 2 * d * 151936
    assert sh.attn_flops(100) == 4 * 8 * 64 * 128 * 100
    # 10 routed pairs on 3 touched experts: 3 matmuls a pair; each
    # touched expert's weights read once; per pair, gate and up each read
    # a row of d and write one of ff, down reads one of ff and writes d
    flops, nbytes = sh.ragged_dot_work(10, 3)
    assert flops == 10 * 2 * 3 * d * ff
    assert nbytes == 3 * 2 * 3 * d * ff + 10 * 2 * (3 * d + 3 * ff)


def test_model_config_holds_the_share():
    cfg = spec.model_config(CONF)
    assert (cfg.moe_experts, cfg.moe_topk) == (128, 8)
    assert cfg.held_experts == range(0, 8)
    assert (cfg.num_layers, cfg.d_model, cfg.moe_dff) == (8, 4096, 1536)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (64, 4, 128)
    assert cfg.qk_norm and not cfg.tie_embeddings
    small = spec.model_config(CONF, rehearse=True)
    assert (small.moe_experts, small.moe_topk) == (16, 4)
    assert small.held_experts == range(0, 4)


def test_fp8_control_moves_the_reference():
    sizes = spec.sizes(CONF, rehearse=True)
    from repro.models import build_model

    params = weights.make_params(
        build_model(spec.model_config(CONF, rehearse=True)).init, 11,
        moe.fan_in)
    toks = np.random.default_rng(0).integers(0, 512, 40)
    want = np.arange(40)
    ref = moe.logits(params, sizes, toks, want)
    low = moe.logits(params, sizes, toks, want, quant="fp8")
    assert ref.shape == (40, 512) and np.isfinite(low).all()
    diff = np.abs(low - ref).max()
    assert 1e-3 < diff < 0.5 * np.abs(ref).max()


#: the rehearsal's limit, and the seeds its readings are checked on
LIMIT = json.loads((BENCH / "limits" / f"{CELL}.json").read_text())[
    "rehearsal"]["max_logit_gap"]["limit"]
SEEDS = range(5, 13)


def _program_routes(cfg, params, seq):
    """The experts the program's own forward routes each token of ``seq``
    to, in every layer (L, len(seq), k): its dropless layer's router,
    read as the layer runs."""
    import repro.models.moe as moe_mod
    from harness.reference import PAD_TO
    from repro.models import build_model

    seen = []
    dropless = moe_mod.moe_dropless

    def spy(p, h, c, valid=None):
        z = h.reshape(-1, h.shape[-1]).astype(jnp.float32) @ \
            p["router"].astype(jnp.float32)
        top = jax.lax.top_k(jax.nn.softmax(z, axis=-1), c.moe_topk)[1]
        jax.debug.callback(lambda a: seen.append(np.asarray(a)), top)
        return dropless(p, h, c, valid)

    padded = np.zeros(-(-len(seq) // PAD_TO) * PAD_TO, np.int32)
    padded[:len(seq)] = seq
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(moe_mod, "moe_dropless", spy)
        jax.block_until_ready(build_model(cfg).forward(
            params, {"tokens": jnp.asarray(padded)[None]},
            return_cache=True)[0])
        jax.effects_barrier()
    return np.stack(seen)[:, :len(seq)]


def _held(idx, sizes):
    first, held = sizes["first_expert"], sizes["num_experts"]
    return np.sort(np.where((idx >= first) & (idx < first + held), idx, -1),
                   axis=-1)


@pytest.fixture(scope="module")
def rehearsal_readings():
    """For each seed, as ``bench/tools/control.py --rehearse`` reads it:
    fresh weights, the cell's traffic served for 2 s and drained, and on
    the run's sample the program's and the float8 control's widest gaps;
    besides, the widest gap of the reference made to follow the
    program's own routing, and the (layer, token) pairs whose held
    experts the program and the reference chose differently."""
    import run as bench_run
    from harness import check, driver
    from repro.models import build_model

    cell = spec.load_cell(CELL, BENCH.parent)
    sizes = spec.sizes(cell.config, True)
    serve = spec.serve_settings(cell.config, True)
    mix_mod = spec.load_module(spec.traffic_module_path(
        cell.traffic["generator"], BENCH.parent))
    mix = mix_mod.scaled(cell.traffic, serve["max_len"] /
                         cell.config["serve"]["max_len"])
    clock = driver.WindowClock()
    cfg, _, engine = bench_run.build(cell, SEEDS[0], True, False, clock)
    init = build_model(cfg).init
    drv = driver.Driver(engine, clock, driver.CompileCounter())
    out = []
    for k, seed in enumerate(SEEDS):
        params = engine.params if k == 0 else weights.make_params(
            init, seed, moe.fan_in)
        engine.params = params
        sched = mix_mod.schedule(mix, seed, 2, sizes["vocab_size"])
        if k == 0:
            drv.warm(sched, sizes["vocab_size"])
        engine.reset()
        win = drv.window(sched, 2)
        done = [check.Served(list(r.req.prompt), list(r.req.generated))
                for r in win.recs if r.done]
        items = check.sample(done, seed, bench_run.SAMPLE_TOKENS,
                             bench_run.SAMPLE_REQUESTS)
        prog = check.served_gaps(moe, params, sizes, items)
        ctrl = check.control_gaps(moe, params, sizes, items)
        follow, differs = [], 0
        for it in items:
            seq = list(it.prompt) + list(it.tokens[:-1])
            want = np.arange(len(it.prompt) - 1, len(seq))
            mine = _program_routes(cfg, params, seq)
            ref = moe.logits(params, sizes, seq, want, routes=mine)
            tok = np.asarray(it.tokens)
            follow.append(ref.max(axis=1) - ref[np.arange(len(tok)), tok])
            differs += int(np.any(_held(mine, sizes) != _held(
                moe.routes(params, sizes, seq), sizes), axis=-1).sum())
        out.append({"seed": seed, "tokens": int(prog.size),
                    "failed": sum(1 for r in win.recs
                                  if r.rejected or not r.done),
                    "program_gap": float(prog.max()),
                    "control_gap": float(ctrl.max()),
                    "following_gap": float(np.concatenate(follow).max()),
                    "held_differs": differs})
    return out


def test_reference_follows_a_given_routing():
    """Made to follow its own routing, the reference gives its own
    logits; made to follow another, other logits."""
    sizes = spec.sizes(CONF, rehearse=True)
    from repro.models import build_model

    params = weights.make_params(
        build_model(spec.model_config(CONF, rehearse=True)).init, 11,
        moe.fan_in)
    toks = np.random.default_rng(1).integers(0, 512, 30)
    want = np.arange(30)
    own = moe.routes(params, sizes, toks)
    assert own.shape == (2, 30, 4)
    ref = moe.logits(params, sizes, toks, want)
    np.testing.assert_array_equal(
        moe.logits(params, sizes, toks, want, routes=own), ref)
    other = own.copy()
    other[0, 5] = (other[0, 5] + 4) % 16      # token 5 of layer 0 elsewhere
    moved = moe.logits(params, sizes, toks, want, routes=other)
    np.testing.assert_array_equal(moved[:5], ref[:5])
    assert not np.allclose(moved[5], ref[5])


def test_control_fails_and_program_passes(rehearsal_readings):
    """Seeds 5-12 at the rehearsal widths: the float8 control fails the
    rehearsal limit on every seed; the reference made to follow the
    program's own routing passes it on every seed; and the program passes
    it wherever it chose the same held experts as the reference.  Where
    it does not (seed 10: two tokens whose top-4 boundary the router's
    probabilities straddle by under 4e-4), bfloat16 tipped a router
    near-tie, and the gap is that of another routing, not of wrong
    arithmetic."""
    assert [r["seed"] for r in rehearsal_readings] == list(SEEDS)
    for r in rehearsal_readings:
        assert r["tokens"] > 0 and r["failed"] == 0, r
        assert r["control_gap"] > LIMIT, r
        assert r["following_gap"] <= LIMIT, r
        if r["program_gap"] > LIMIT:
            assert r["held_differs"] > 0, r


def test_rehearsal_of_the_cell_is_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", CELL,
         "--seed", "3000000007", "--seconds", "6", "--trace", "1",
         "--rehearse"], env=env, capture_output=True, text=True,
        timeout=900, check=True).stdout
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0, line
    assert line["compiles_in_window"] == 0


def test_ragged_dot_roofline_reader_by_hand():
    """A decode tick holding two grouped-matmul events (3 ms) and a
    fusion; a third grouped matmul outside any span of the expert layer;
    100 routed pairs touching 10 held experts: the least time is the
    touched experts' weights and the kernels' rows at 819 GB/s."""
    import types

    from harness.peaks import peaks_for
    from repro.obs.trace import SpanRecord

    ms, t0 = 1_000_000, 5.0
    tpu = ' custom-call(...), custom_call_target="tpu_custom_call"'
    tr = {"devices": {"/device:TPU:0": [
        ["%fusion.1 = bf16[64]", 10 * ms, 10 * ms, ""],
        ["%ragged-dot-none.1 = bf16[512,1536]" + tpu, 20 * ms, 2 * ms, ""],
        ["%ragged-dot-none.2 = bf16[512,4096]" + tpu, 23 * ms, 1 * ms, ""],
        ["%ragged-dot-none.3 = bf16[512,4096]" + tpu, 80 * ms, 1 * ms, ""]]},
        "host": [["bench.run_step", 0, 55 * ms],
                 ["bench.run_step", 58 * ms, 100 * ms]]}

    def span(sid, name, a, b, **attrs):
        return SpanRecord(name=name, t0=t0 + a / 1e3, dur=(b - a) / 1e3,
                          attrs=attrs, sid=sid, parent=None, tid=1)

    tick = {"moe_routed": 100, "moe_touched": 10, "moe_max_load": 30}
    spans = [span(1, "step", 1, 54), span(2, "decode_tick", 8, 52, **tick),
             span(3, "step", 59, 99), span(4, "report", 72, 99)]
    peaks = peaks_for("TPU v5 lite")
    run = types.SimpleNamespace(trace=tr, traced=(t0, t0 + 0.1), spans=spans,
                                shapes=moe.shapes(spec.sizes(CONF)),
                                peaks=peaks)
    reader = spec.load_module(spec.metric_module_path("ragged_dot_roofline"))
    nbytes = 10 * 2 * 3 * 4096 * 1536 + 100 * 2 * 3 * (4096 + 1536)
    flops = 100 * 2 * 3 * 4096 * 1536
    least = max(nbytes / peaks.hbm_bytes_per_s, flops / peaks.bf16_flops)
    assert reader.read(run) == pytest.approx(100 * least / 3e-3)
    # the parent's spans carry no expert attributes: nothing to read
    spans[1] = span(2, "decode_tick", 8, 52)
    assert reader.read(run) is None
