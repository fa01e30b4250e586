"""The sparse-expert decoder family (Qwen3-MoE layers): everything the
harness knows of a configuration whose file says ``"family": "moe"``.

* ``model_config``: the published keys -> the program's ``ModelConfig``
  (an ``ExpertShare``: the experts this chip holds of each layer);
* ``REHEARSAL_SIZES``: tiny widths for the CPU rehearsal;
* ``fan_in``: the fan-in of one (unstacked) weight, for ``weights.py``;
* ``logits``: the plain reference, float32 at the highest matmul
  precision, with no kernel, cache, batching or chunking; it imports
  nothing of the program.  ``quant="fp8"`` is the control, and
  ``routes`` makes it follow another computation's routing (``routes``
  gives its own);
* ``shapes``: the work counts the metric readers use (``harness/work.py``
  and ``ragged_dot_work`` for the expert layer's grouped matmuls).

The configuration file holds one chip's share of an expert-parallel
layer: ``num_experts`` is the count of experts held here, from
``first_expert`` on, and ``router_experts`` the router's published
width.  Every layer is

    h = x + o(attn(rope(qnorm(q(rms(x)))), rope(knorm(k(rms(x)))), v(rms(x))))
    y = h + sum_{i in top-k, i held} g_i down_i(silu(gate_i(rms(h))) * up_i(rms(h)))

with ``g`` the softmax of the float32 router logits over all
``router_experts``, renormalised over the top k (``norm_topk_prob``).
What the experts held on other chips would add is left out, in the
reference as in the program.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from harness.reference import PAD_TO, F32, attention, mm, rms, rope, run

#: configuration-file keys (published names) -> the program's
#: ``ModelConfig`` fields
HF_TO_MODEL = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "moe_intermediate_size": "moe_dff",
    "num_experts_per_tok": "moe_topk",
    "router_experts": "moe_experts",
    "first_expert": "moe_first",
    "num_experts": "moe_held",
    "vocab_size": "vocab_size",
    "head_dim": "head_dim",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
}

#: tiny widths for the CPU rehearsal (never a device number): a router
#: of 16 experts, top 4, of which this chip holds 4
REHEARSAL_SIZES = {"num_hidden_layers": 2, "hidden_size": 128,
                   "num_attention_heads": 4, "num_key_value_heads": 2,
                   "head_dim": 32, "moe_intermediate_size": 64,
                   "router_experts": 16, "num_experts_per_tok": 4,
                   "first_expert": 0, "num_experts": 4, "vocab_size": 512}

#: the expert weights, stacked (experts, in, out)
EXPERT_LEAVES = frozenset({"w_gate", "w_up", "w_down"})


def model_config(sizes: dict, name: str):
    """The program's ``ModelConfig`` for the experts held here: every
    size from ``sizes``, nothing from the program's registry."""
    from repro.configs.base import ExpertShare

    if sizes["model_type"] != "qwen3_moe" or sizes["hidden_act"] != "silu":
        raise ValueError(f"unsupported {sizes['model_type']!r} / "
                         f"{sizes['hidden_act']!r}")
    if sizes["decoder_sparse_step"] != 1 or sizes["mlp_only_layers"] \
            or not sizes["norm_topk_prob"]:
        raise ValueError("every layer must be sparse, with renormalised "
                         "top-k gates")
    kw = {field: sizes[key] for key, field in HF_TO_MODEL.items()}
    # d_ff: the dense MLP width, which no layer of this model has
    return ExpertShare(name=name, family="moe", mlp_act="swiglu",
                       qk_norm=True, dtype=sizes["torch_dtype"],
                       d_ff=sizes["moe_intermediate_size"], **kw)


def fan_in(name: str, shape: tuple) -> int:
    """An expert weight (E, in, out) gives ``in``; the output projection
    (heads, head_dim, d) gives heads x head_dim; every other matrix its
    first axis (the router (d, E) gives d)."""
    if name in EXPERT_LEAVES and len(shape) == 3:
        return shape[1]
    if name == "wo":
        return shape[0] * shape[1]
    return shape[0]


# -- the plain reference ---------------------------------------------------

def _experts(x, m, sz, quant, route=None):
    """The held experts' part of one layer's expert output, x (S, d), and
    the top-k experts each token is routed to (S, k); ``route`` (S, k)
    replaces the router's choice (its gates stay the router's)."""
    k, first, held = (sz["num_experts_per_tok"], sz["first_expert"],
                      sz["num_experts"])
    probs = jax.nn.softmax(mm("sd,de->se", x, m["router"], quant, -1, 0),
                           axis=-1)
    top, idx = jax.lax.top_k(probs, k)
    if route is not None:
        idx = route
        top = jnp.take_along_axis(probs, idx, axis=1)
    top = top / top.sum(-1, keepdims=True)
    ids = first + jnp.arange(held)
    # each token's gate on each held expert (0 where it is not routed)
    gate = jnp.sum(jnp.where(idx[:, :, None] == ids, top[:, :, None], 0.0),
                   axis=1)
    g = mm("sd,edf->sef", x, m["w_gate"], quant, -1, 1)
    u = mm("sd,edf->sef", x, m["w_up"], quant, -1, 1)
    o = mm("sef,efd->sed", jax.nn.silu(g) * u, m["w_down"], quant, -1, 1)
    return jnp.einsum("se,sed->sd", gate, o,
                      precision=jax.lax.Precision.HIGHEST), idx


def _forward(params, tokens, want, *, sz, quant, routes=None,
             choice=False):
    """Logits at ``want``; with ``choice`` the experts each layer routed
    each token to (L, S, k) instead."""
    eps, theta = float(sz["rms_norm_eps"]), float(sz["rope_theta"])
    h, g, hd = (sz["num_attention_heads"], sz["num_key_value_heads"],
                sz["head_dim"])
    tok = params["embed"]["tok"]
    x = tok[tokens].astype(F32)
    pos = jnp.arange(tokens.shape[0])

    def layer(x, pr):
        p, route = pr
        p = jax.tree.map(lambda a: a.astype(F32), p)
        a = p["attn"]
        hn = rms(x, p["ln1"], eps)
        q = rms(mm("sd,dhk->shk", hn, a["wq"], quant, -1, 0), a["q_norm"],
                eps)
        k = rms(mm("sd,dgk->sgk", hn, a["wk"], quant, -1, 0), a["k_norm"],
                eps)
        v = mm("sd,dgk->sgk", hn, a["wv"], quant, -1, 0)
        q, k = rope(q, pos, theta), rope(k, pos, theta)
        o = attention(q.reshape(-1, g, h // g, hd), k, v)
        o = o.reshape(-1, h, hd)
        x = x + mm("shk,hkd->sd", o, a["wo"], quant, (-2, -1), (0, 1))
        y, idx = _experts(rms(x, p["ln2"], eps), p["moe"], sz, quant, route)
        return x + y, idx

    x, idx = jax.lax.scan(layer, x, (params["blocks"], routes))
    if choice:
        return idx
    xw = rms(x[want], params["ln_f"].astype(F32), eps)
    return mm("sd,dv->sv", xw, params["embed"]["unembed"].astype(F32),
              quant, -1, 0)


#: the sizes ``_forward`` reads
_REF_KEYS = ("num_hidden_layers", "hidden_size", "num_attention_heads",
             "num_key_value_heads", "head_dim", "rms_norm_eps", "rope_theta",
             "num_experts_per_tok", "first_expert", "num_experts")


def _padded(sizes: dict, tokens, routes=None):
    if sizes["tie_word_embeddings"]:
        raise ValueError("the reference reads an untied head")
    n = -(-len(tokens) // PAD_TO) * PAD_TO
    if routes is not None:
        routes = np.asarray(routes, np.int32)
        routes = np.pad(routes, ((0, 0), (0, n - routes.shape[1]), (0, 0)))
    return tuple((k, sizes.get(k)) for k in _REF_KEYS), routes


def logits(params, sizes: dict, tokens, want, quant=None, routes=None):
    """float32 logits (len(want), vocab) of the sequence ``tokens`` at
    the positions ``want``.  ``routes`` (layers, len(tokens), k), expert
    ids, makes every layer route each token to those experts instead of
    the router's top k (the gates stay the router's probabilities,
    renormalised over them): the reference then follows another
    computation's routing, for telling a router near-tie that flipped
    from a wrong result."""
    sz, routes = _padded(sizes, tokens, routes)
    forward = _forward if routes is None else functools.partial(
        _forward, routes=jnp.asarray(routes))
    return run(forward, sz, params, tokens, want, quant)


def routes(params, sizes: dict, tokens) -> np.ndarray:
    """The experts the reference routes each token to, in every layer:
    (layers, len(tokens), k) ids."""
    sz, _ = _padded(sizes, tokens)
    padded = np.zeros(-(-len(tokens) // PAD_TO) * PAD_TO, np.int32)
    padded[:len(tokens)] = tokens
    out = jax.jit(functools.partial(_forward, sz=dict(sz), quant=None,
                                    choice=True))(
        params, jnp.asarray(padded), jnp.zeros(1, jnp.int32))
    return np.asarray(out)[:, :len(tokens)]


# -- work counts -----------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Shapes:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    experts: int              # the router's width
    topk: int
    held: int                 # experts held here
    expert_ff: int
    act_bytes: int = 2        # bf16 activations
    kv_bytes: int = 2         # pool element size
    weight_bytes: int = 2     # bf16 weights

    @property
    def expert_params(self) -> int:
        """Weights of one expert: gate, up and down."""
        return 3 * self.d * self.expert_ff

    @property
    def layer_matmul_params(self) -> int:
        """Weights one token multiplies per layer, on average: q, k, v, o,
        the router, and ``topk * held / experts`` expert pairs (the pairs
        routed to the experts held here, with routing spread evenly)."""
        d, h, g, hd = self.d, self.heads, self.kv_heads, self.head_dim
        return (d * h * hd + 2 * d * g * hd + h * hd * d + d * self.experts
                + self.topk * self.held * self.expert_params // self.experts)

    @property
    def token_flops(self) -> int:
        """Matmul FLOPs of one token through every layer (no attention
        scores, no head)."""
        return 2 * self.layers * self.layer_matmul_params

    @property
    def head_flops(self) -> int:
        """FLOPs of one token's logits."""
        return 2 * self.d * self.vocab

    def attn_flops(self, keys: int) -> int:
        """Attention FLOPs over all layers for ``keys`` (query, key)
        pairs per head."""
        return 4 * self.layers * self.heads * self.head_dim * keys

    def ragged_dot_work(self, pairs: int, touched: int) -> tuple:
        """(FLOPs, bytes) the expert layer's three grouped matmuls need
        for ``pairs`` routed (token, held expert) pairs that touch
        ``touched`` held experts, both summed over layers: each pair's
        gate, up and down matmuls; each touched expert's weights read
        once, and each kernel's rows in and out (gate and up read the
        pair's row of width d and write one of ``expert_ff``; down reads
        one of ``expert_ff`` and writes one of d)."""
        flops = 2 * self.expert_params * pairs
        rows = 3 * (self.d + self.expert_ff) * pairs
        nbytes = (self.weight_bytes * self.expert_params * touched
                  + self.act_bytes * rows)
        return flops, nbytes


def shapes(s: dict, kv_bytes: int = 2) -> Shapes:
    return Shapes(layers=s["num_hidden_layers"], d=s["hidden_size"],
                  heads=s["num_attention_heads"],
                  kv_heads=s["num_key_value_heads"], head_dim=s["head_dim"],
                  vocab=s["vocab_size"], experts=s["router_experts"],
                  topk=s["num_experts_per_tok"], held=s["num_experts"],
                  expert_ff=s["moe_intermediate_size"], kv_bytes=kv_bytes)
