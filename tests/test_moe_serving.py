"""A MoE share served through ``ServeEngine``: chunked prefill, paged
pool, fused paged decode, and the dropless expert layer.

The configuration is one chip's share of a 16-expert top-4 layer (experts
4-7, rank 1 of 4) at small widths, in float32 so that the engine can be
held to a plain float32 forward pass.  Each prompt is prefilled in
chunks of 16, so long prompts take several chunks.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import ExpertShare
from repro.models import build_model
from repro.obs.trace import Tracer
from repro.serve import ServeEngine
from repro.tuner import TuningCache

PROMPTS = [[7, 3, 99, 5, 12], list(range(40, 77)), [250, 1, 8, 9, 31, 64, 2]]
MAX_NEW = 6


def _share(experts, topk, first, held):
    base = dataclasses.replace(get_config("qwen3-moe-235b-a22b").reduced(),
                               dtype="float32", moe_experts=experts,
                               moe_topk=topk)
    cfg = ExpertShare.of(base, first, held)
    params = build_model(cfg).init(jax.random.key(0))
    keys = iter(jax.random.split(jax.random.key(1), 64))

    def widen(path, a):
        # norm gains away from 1, matrices at about 1/sqrt(fan-in), so
        # that every part of the layer changes what is served
        name = str(getattr(path[-1], "key", path[-1]))
        if name.startswith(("ln", "q_norm", "k_norm")):
            return 0.1 * jax.random.normal(next(keys), a.shape)
        return a * 4.0 if a.ndim >= 3 else a

    return cfg, jax.tree_util.tree_map_with_path(widen, params)


@pytest.fixture(scope="module")
def share():
    return _share(16, 4, 4, 4)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + g)


def _rope(x, theta):
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
        * theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def reference_logits(cfg, params, tokens):
    """Plain float32 forward over the whole sequence: (S, vocab)."""
    eps, g, hd = cfg.norm_eps, cfg.num_kv_heads, cfg.head_dim
    x = params["embed"]["tok"][jnp.asarray(tokens)]
    s = x.shape[0]
    causal = jnp.tril(jnp.ones((s, s), bool))
    held = cfg.held_experts
    for layer in range(cfg.num_layers):
        p = jax.tree.map(lambda a: a[layer], params["blocks"])
        a, m = p["attn"], p["moe"]
        h = _rms(x, p["ln1"], eps)
        q = _rope(_rms(jnp.einsum("sd,dhk->shk", h, a["wq"]), a["q_norm"],
                       eps), cfg.rope_theta)
        k = _rope(_rms(jnp.einsum("sd,dgk->sgk", h, a["wk"]), a["k_norm"],
                       eps), cfg.rope_theta)
        v = jnp.einsum("sd,dgk->sgk", h, a["wv"])
        q = q.reshape(s, g, -1, hd)
        sc = jnp.einsum("qgrd,kgd->grqk", q, k) * hd ** -0.5
        w = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), -1)
        o = jnp.einsum("grqk,kgd->qgrd", w, v).reshape(s, -1, hd)
        x = x + jnp.einsum("shk,hkd->sd", o, a["wo"])
        h = _rms(x, p["ln2"], eps)
        probs = jax.nn.softmax(h @ m["router"], -1)
        top, idx = jax.lax.top_k(probs, cfg.moe_topk)
        top = top / top.sum(-1, keepdims=True)
        for j, e in enumerate(held):
            gate = jnp.sum(jnp.where(idx == e, top, 0.0), -1)
            y = (jax.nn.silu(h @ m["w_gate"][j]) * (h @ m["w_up"][j])) \
                @ m["w_down"][j]
            x = x + gate[:, None] * y
    return _rms(x, params["ln_f"], eps) @ params["embed"]["unembed"]


def _serve(cfg, params, prompts, tracer=None, slots=4, max_new=None,
           prefill_chunk=16):
    """Serve ``prompts`` (submitted together; ``max_new`` tokens each,
    ``MAX_NEW`` by default) and capture the logits the engine computed
    for each served token: {(request index, token index): logits}."""
    eng = ServeEngine(cfg, slots=slots, max_len=128, params=params,
                      tuning_cache=TuningCache(path=None),
                      prefill_chunk=prefill_chunk, tracer=tracer)
    reqs = [eng.submit(p, max_new_tokens=n) for p, n in
            zip(prompts, max_new or [MAX_NEW] * len(prompts))]
    index = {r.rid: i for i, r in enumerate(reqs)}
    got = {}
    decode, chunk = eng._decode, eng._chunk_step

    def spy_decode(*a, **kw):
        rows = [(slot, r.rid, len(r.generated)) for slot, r in
                eng.scheduler.live_by_slot().items()
                if not r.done and r.rid not in eng._prefilling]
        out = decode(*a, **kw)
        lg = np.asarray(out[0])[:, 0]
        for slot, rid, n in rows:
            got[(index[rid], n)] = lg[slot]
        return out

    def spy_chunk(params, cache, toks, n, **kw):
        task = eng._chunk_tasks[0]
        out = chunk(params, cache, toks, n, **kw)
        if task.done + int(n) >= len(task.toks):
            got[(index[task.req.rid], 0)] = np.asarray(out[0])[0, int(n) - 1]
        return out

    eng._decode, eng._chunk_step = spy_decode, spy_chunk
    report = eng.run()
    assert report.summary.n_completed == len(prompts)
    return [list(r.generated) for r in reqs], got


def test_engine_matches_float32_reference(share):
    """Chunked prefill, then decoding through the paged pool: every
    served token's logits equal the full forward's at its position."""
    cfg, params = share
    outs, got = _serve(cfg, params, PROMPTS)
    assert len(got) == len(PROMPTS) * MAX_NEW
    for i, (prompt, out) in enumerate(zip(PROMPTS, outs)):
        ref = np.asarray(reference_logits(cfg, params, prompt + out[:-1]))
        for n in range(MAX_NEW):
            np.testing.assert_allclose(got[(i, n)], ref[len(prompt) - 1 + n],
                                       rtol=1e-4, atol=1e-4,
                                       err_msg=f"request {i}, token {n}")


def test_request_logits_independent_of_tick_mates():
    """A request's logits are the same served alone and as the last of
    8 rows that decode together.  Half the router's experts are held
    here and each token picks half of them, so a capacity of 1.25 x the
    even share per expert would push the last row's pairs out on most
    ticks; dropless, no other row can."""
    cfg, params = _share(8, 4, 4, 4)
    mine = PROMPTS[2]
    _, got_alone = _serve(cfg, params, [mine], slots=8)
    mates = [[3 + i, 17, 5 * i + 1] for i in range(7)]
    _, got_together = _serve(cfg, params, mates + [mine], slots=8,
                             max_new=[24] * 7 + [MAX_NEW])
    for n in range(MAX_NEW):
        np.testing.assert_allclose(got_together[(7, n)], got_alone[(0, n)],
                                   rtol=1e-6, atol=1e-6)


def test_steps_carry_the_expert_load_into_spans(share):
    """moe ticks and chunks carry the load attributes; a tick's routed
    pairs are at most its rows x top-k x layers; dense spans carry none."""
    cfg, params = share
    tr = Tracer()
    _serve(cfg, params, PROMPTS, tracer=tr)
    ticks = [s for s in tr.spans() if s.name == "decode_tick"]
    chunks = [s for s in tr.spans() if s.name == "prefill_chunk"]
    assert ticks and chunks
    for sp in ticks + chunks:
        a = sp.attrs
        assert a["moe_touched"] <= cfg.num_layers * cfg.moe_held
        assert a["moe_max_load"] <= a["moe_routed"]
    for sp in ticks:
        assert sp.attrs["moe_routed"] <= \
            len(PROMPTS) * cfg.moe_topk * cfg.num_layers
    # a 5-token prompt's one chunk routes at most 5 x 4 pairs a layer
    assert chunks[0].attrs["moe_routed"] <= 5 * 4 * cfg.num_layers

    dense = dataclasses.replace(get_config("smollm-135m").reduced(),
                                dtype="float32")
    tr = Tracer()
    eng = ServeEngine(dense, slots=2, max_len=64,
                      tuning_cache=TuningCache(path=None), tracer=tr)
    eng.submit([1, 2, 3], max_new_tokens=3)
    eng.run()
    assert not any(k.startswith("moe_") for s in tr.spans()
                   for k in s.attrs)


def test_auto_chunk_caps_routed_pairs(share, monkeypatch):
    """With experts, an "auto" chunk routes at most CHUNK_PAIRS pairs
    (its width a power of two); a dense model keeps the tuned block_q.
    Served at a cap of 16 tokens, the long prompt takes three chunks and
    every token is the one the fixed 16-token chunks serve."""
    from repro.serve import engine as engine_mod

    cfg, params = share
    eng = ServeEngine(cfg, slots=2, max_len=64, params=params,
                      tuning_cache=TuningCache(path=None))
    assert cfg.moe_topk == 4 and engine_mod.CHUNK_PAIRS == 2048
    assert eng._chunk_size((1024, 512)) == 512
    assert eng._chunk_size((128, 128)) == 128
    dense = dataclasses.replace(get_config("smollm-135m").reduced(),
                                dtype="float32")
    eng = ServeEngine(dense, slots=2, max_len=64,
                      tuning_cache=TuningCache(path=None))
    assert eng._chunk_size((1024, 512)) == 1024

    monkeypatch.setattr(engine_mod, "CHUNK_PAIRS", 70)   # 17 -> 16 tokens
    tr = Tracer()
    outs, got = _serve(cfg, params, PROMPTS, tracer=tr,
                       prefill_chunk="auto")
    chunks = [s.attrs for s in tr.spans() if s.name == "prefill_chunk"]
    assert max(a["chunk"] for a in chunks) == 16
    rids = sorted({a["rid"] for a in chunks})
    # prompts of 5, 37 and 7 tokens
    assert [sum(a["rid"] == r for a in chunks) for r in rids] == [1, 3, 1]
    fixed, got_fixed = _serve(cfg, params, PROMPTS)
    assert outs == fixed
    for key, lg in got_fixed.items():
        np.testing.assert_allclose(got[key], lg, rtol=1e-6, atol=1e-6)
