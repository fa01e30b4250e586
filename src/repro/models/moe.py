"""Mixture-of-Experts layer: fine-grained experts, capacity routing, EP,
and the dropless layer that serving runs.

Dispatch is **sort-based** (argsort expert ids -> position-in-expert ->
scatter into (E, C, d) buffers), not the one-hot einsum some frameworks
use: the einsum dispatch costs O(T·E·C·d) MACs, the sort costs
O(T·k·(log T + d)) — at qwen3-moe scale that is a ~100x useful-flops
difference, directly visible in the roofline's MODEL_FLOPS/HLO_FLOPs ratio.

Capacity is Eq. 1 over routed token slots (``core.mapper.plan_moe_capacity``):
gws = T·k slots across hp = E expert lanes, with the standard slack factor;
overflow tokens are dropped (written to a trash row), underflow slots are
zero — the MoE instance of the paper's exact-fit regime.

Experts are sharded over the ``model`` axis (EP); GSPMD materializes the
token all-to-all from the sharding annotations.

Serving runs ``moe_dropless`` instead (the step decides, not a flag):
no capacity, so no (token, expert) pair is ever dropped and a token's
output never depends on the other tokens of its batch.  It computes the
part of the result that this chip's experts give (``cfg.held_experts``,
one rank's share of an expert-parallel layer) with one grouped matmul
per weight over the pairs routed to them.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.configs.base import ModelConfig
from repro.core.mapper import MappingPolicy, plan_moe_capacity
from repro.models.layers import ParamSpec, ShardCtx


def moe_specs(cfg: ModelConfig) -> dict:
    """The router spans every expert; the expert weights are the held ones."""
    d, e, ff = cfg.d_model, len(cfg.held_experts), cfg.moe_dff
    s = {
        "router": ParamSpec((d, cfg.moe_experts), ("embed", "experts_r")),
        "w_gate": ParamSpec((e, d, ff), ("experts", "embed", None)),
        "w_up": ParamSpec((e, d, ff), ("experts", "embed", None)),
        "w_down": ParamSpec((e, ff, d), ("experts", None, "embed")),
    }
    if cfg.moe_shared_experts:
        sf = cfg.moe_shared_experts * ff
        s["shared"] = {
            "w_gate": ParamSpec((d, sf), ("embed", "mlp")),
            "w_up": ParamSpec((d, sf), ("embed", "mlp")),
            "w_down": ParamSpec((sf, d), ("mlp", "embed")),
        }
    return s


def _act(g, u, act: str):
    return (jax.nn.silu(g) if act == "swiglu" else jax.nn.gelu(g)) * u


def _route_one_group(x, router, e: int, k: int, c: int, act: str):
    """Sort-based dispatch + combine for ONE data-shard group of tokens.

    x: (t, d) local tokens.  Returns (expert_in (E,C,d), combine closure
    state, aux).  vmapped over the group axis so all index arithmetic is
    group-local — no cross-shard gathers, the only cross-device movement
    is the (G, E, C, d) buffer resharding (the all-to-all) handled by
    GSPMD from the sharding annotations.
    """
    t, d = x.shape
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = jax.lax.top_k(probs, k)                  # (t, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    # Switch-style aux loss: E * sum_e f_e * p_e (computed per group)
    assign = jnp.zeros((t, e), jnp.float32).at[
        jnp.arange(t)[:, None], eidx].add(1.0)
    aux = e * jnp.sum((assign.mean(0) / k) * probs.mean(0))

    flat_e = eidx.reshape(-1)                              # (t*k,)
    flat_gate = gates.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(t), k)
    order = jnp.argsort(flat_e)
    se, stok, sgate = flat_e[order], flat_tok[order], flat_gate[order]
    starts = jnp.searchsorted(se, jnp.arange(e))           # (e,)
    pos = jnp.arange(t * k) - starts[se]
    keep = pos < c
    dest = jnp.where(keep, se * c + pos, e * c)            # trash row at end

    buf = jnp.zeros((e * c + 1, d), x.dtype)
    buf = buf.at[dest].set(x[stok], mode="drop")
    return buf[:e * c].reshape(e, c, d), (dest, stok, sgate, keep), aux


def _combine_one_group(out_e, state, t: int, dtype):
    dest, stok, sgate, keep = state
    e_c, d = out_e.shape[0] * out_e.shape[1], out_e.shape[2]
    flat_out = jnp.concatenate(
        [out_e.reshape(e_c, d), jnp.zeros((1, d), out_e.dtype)], 0)
    y_slots = flat_out[dest] * sgate[:, None].astype(out_e.dtype)
    return jnp.zeros((t, d), dtype).at[stok].add(
        jnp.where(keep[:, None], y_slots, 0))


def moe_mlp(
    params: dict,
    h: jax.Array,                       # (B, S, d)
    cfg: ModelConfig,
    ctx: ShardCtx,
    *,
    capacity: Optional[int] = None,
    policy: MappingPolicy = MappingPolicy.AUTO,
) -> tuple[jax.Array, jax.Array]:
    """Returns (output (B,S,d), aux load-balance loss).

    Routing is GROUP-LOCAL (GShard style): tokens are split into
    ``moe_groups`` groups aligned with the data shards; each group routes
    its own tokens into per-group (E, C_local, d) buffers.  All sort /
    scatter indexing stays within a shard; GSPMD turns the group-sharded
    -> expert-sharded einsum into the EP all-to-all.
    """
    b, s, d = h.shape
    e, k = cfg.moe_experts, cfg.moe_topk
    if len(cfg.held_experts) != e:
        raise ValueError(f"{cfg.name}: capacity routing needs every expert "
                         f"held; serving runs moe_dropless")
    t = b * s
    groups = int(ctx.flag("moe_groups", 1))
    while t % groups:
        groups //= 2
    groups = max(groups, 1)
    tl = t // groups                                       # tokens per group
    x = h.reshape(groups, tl, d)
    x = ctx.p(x, "moe_group", None, None)

    if capacity is None:
        slack = float(ctx.flag("moe_slack", 1.25))
        capacity = plan_moe_capacity(tl, e, k, ep_size=1, policy=policy,
                                     slack=slack)
    c = min(capacity, tl)

    expert_in, st, aux = jax.vmap(
        lambda xx: _route_one_group(xx, params["router"], e, k, c,
                                    cfg.mlp_act))(x)
    aux = aux.mean()
    # beyond-paper §Perf lever: ship the dispatch/combine all-to-all in
    # fp8 (per-tensor scale folds into the expert weights) — halves the
    # dominant EP collective traffic.
    fp8 = bool(ctx.flag("moe_fp8_a2a", False))
    if fp8:
        expert_in = expert_in.astype(jnp.float8_e4m3fn)
    expert_in = ctx.p(expert_in, "moe_group", "experts", None, None)
    # named checkpoint: with remat="moe" the recompute pass restarts from
    # the saved (post-all-to-all) buffers instead of re-dispatching
    expert_in = checkpoint_name(expert_in, "moe_in")
    if fp8:
        expert_in = expert_in.astype(h.dtype)

    # ---- expert compute (EP over `experts`) --------------------------- #
    g = jnp.einsum("gecd,edf->gecf", expert_in, params["w_gate"])
    u = jnp.einsum("gecd,edf->gecf", expert_in, params["w_up"])
    out_e = jnp.einsum("gecf,efd->gecd", _act(g, u, cfg.mlp_act),
                       params["w_down"])
    if fp8:
        out_e = out_e.astype(jnp.float8_e4m3fn)
    out_e = ctx.p(out_e, "moe_group", "experts", None, None)
    if fp8:
        out_e = out_e.astype(h.dtype)

    y = jax.vmap(lambda oo, ss: _combine_one_group(oo, ss, tl, h.dtype))(out_e, st)
    y = ctx.p(y, "moe_group", None, None)

    # ---- shared experts ------------------------------------------------ #
    if "shared" in params:
        sp = params["shared"]
        xf = x.reshape(t, d)
        g2 = jnp.einsum("td,df->tf", xf, sp["w_gate"])
        u2 = jnp.einsum("td,df->tf", xf, sp["w_up"])
        hh = ctx.p(_act(g2, u2, cfg.mlp_act), None, "mlp")
        y = y.reshape(t, d) + jnp.einsum("tf,fd->td", hh, sp["w_down"])

    return y.reshape(b, s, d), aux.astype(jnp.float32)


def moe_dropless(params: dict, h: jax.Array, cfg: ModelConfig,
                 valid: Optional[jax.Array] = None
                 ) -> tuple[jax.Array, jax.Array]:
    """The serving expert layer: ``h`` (..., d) -> (output (..., d), load
    (held,) int32).

    Router logits in float32 over all ``moe_experts``, softmax, top-k,
    gates renormalised over the k.  The (token, expert) pairs whose expert
    this chip holds, of the tokens ``valid`` marks (default: all), are
    sorted by expert and run through one grouped matmul per weight
    (``jax.lax.ragged_dot``); every other pair sorts after them and adds
    nothing.  Each pair's output, times its gate, is added back to its
    token.  ``load`` counts the pairs on each held expert.  A shared
    expert, which every chip computes alike, is added whole."""
    shape, d = h.shape, h.shape[-1]
    x = h.reshape(-1, d)
    t, k = x.shape[0], cfg.moe_topk
    held = cfg.held_experts
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    gates, eidx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    gates = gates / gates.sum(-1, keepdims=True)
    mine = (eidx >= held.start) & (eidx < held.stop)
    if valid is not None:
        mine = mine & valid.reshape(t, 1)
    # pairs of held experts first, by expert; the rest share a last key
    key = jnp.where(mine, eidx - held.start, len(held)).reshape(-1)
    order = jnp.argsort(key, stable=True)
    load = jnp.sum(key[:, None] == jnp.arange(len(held)), axis=0,
                   dtype=jnp.int32)
    # one named scope over the expert computation, in its operations' HLO
    # op_name metadata; the v5e's device trace carries no metadata, and
    # there the grouped matmuls are XLA's kernel ``%ragged-dot-none``
    with jax.named_scope("moe_experts"):
        tok = order // k
        xs = x[tok]
        g = jax.lax.ragged_dot(xs, params["w_gate"], load)
        u = jax.lax.ragged_dot(xs, params["w_up"], load)
        out = jax.lax.ragged_dot(_act(g, u, cfg.mlp_act), params["w_down"],
                                 load)
        keep = key[order] < len(held)
        w = jnp.where(keep, gates.reshape(-1)[order], 0.0)
        # rows past the held pairs are not computed: select, not multiply
        contrib = jnp.where(keep[:, None],
                            out.astype(jnp.float32) * w[:, None], 0.0)
        y = jnp.zeros((t, d), jnp.float32).at[tok].add(contrib)
    y = y.astype(h.dtype)
    if "shared" in params:
        sp = params["shared"]
        y = y + jnp.einsum("tf,fd->td", _act(
            jnp.einsum("td,df->tf", x, sp["w_gate"]),
            jnp.einsum("td,df->tf", x, sp["w_up"]), cfg.mlp_act),
            sp["w_down"])
    return y.reshape(shape), load
