"""The dense decoder family (Llama / Qwen3 dense layers): everything the
harness knows of a configuration whose file says ``"family": "dense"``.

* ``model_config``: the published keys -> the program's ``ModelConfig``;
* ``REHEARSAL_SIZES``: tiny widths for the CPU rehearsal;
* ``fan_in``: the fan-in of one (unstacked) weight, for ``weights.py``;
* ``logits``: the plain reference, float32 at the highest matmul
  precision, with no kernel, cache, batching or chunking; it imports
  nothing of the program.  ``quant="fp8"`` is the control;
* ``shapes``: the work counts the metric readers use (``harness/work.py``).

The reference reads the weights as a dict keyed by the program's leaf
names (``embed/tok``, ``blocks/attn/wq`` ...), stacked over layers, and
upcasts one layer at a time inside a scan, so float32 copies of all the
weights never exist at once.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from harness.reference import F32, attention, mm, rms, rope, run

#: configuration-file keys (published names) -> the program's
#: ``ModelConfig`` fields
HF_TO_MODEL = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "head_dim": "head_dim",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
}

#: tiny widths for the CPU rehearsal (never a device number)
REHEARSAL_SIZES = {"num_hidden_layers": 2, "hidden_size": 128,
                   "num_attention_heads": 4, "num_key_value_heads": 2,
                   "head_dim": 32, "intermediate_size": 256,
                   "vocab_size": 512}

#: input axes of each matrix: the output projection contracts heads and
#: head_dim; every other matrix its first axis
FAN_IN_AXES = {"wo": 2}


def model_config(sizes: dict, name: str):
    """The program's ``ModelConfig``: every size from ``sizes``, nothing
    from the program's registry."""
    from repro.configs.base import ModelConfig

    if sizes["hidden_act"] != "silu":
        raise ValueError(f"unsupported hidden_act {sizes['hidden_act']!r}")
    kw = {field: sizes[key] for key, field in HF_TO_MODEL.items()}
    return ModelConfig(name=name, family="dense", mlp_act="swiglu",
                       qk_norm=bool(sizes.get("qk_norm", False)),
                       dtype=sizes["torch_dtype"], **kw)


def fan_in(name: str, shape: tuple) -> int:
    return math.prod(shape[:FAN_IN_AXES.get(name, 1)])


# -- the plain reference ---------------------------------------------------

def _forward(params, tokens, want, *, sz, quant):
    eps, theta = float(sz["rms_norm_eps"]), float(sz["rope_theta"])
    h, g, hd = (sz["num_attention_heads"], sz["num_key_value_heads"],
                sz["head_dim"])
    qk_norm = bool(sz.get("qk_norm", False))
    tok = params["embed"]["tok"]
    x = tok[tokens].astype(F32)
    pos = jnp.arange(tokens.shape[0])

    def layer(x, p):
        p = jax.tree.map(lambda a: a.astype(F32), p)
        a = p["attn"]
        hn = rms(x, p["ln1"], eps)
        q = mm("sd,dhk->shk", hn, a["wq"], quant, -1, 0)
        k = mm("sd,dgk->sgk", hn, a["wk"], quant, -1, 0)
        v = mm("sd,dgk->sgk", hn, a["wv"], quant, -1, 0)
        if qk_norm:
            q = rms(q, a["q_norm"], eps)
            k = rms(k, a["k_norm"], eps)
        q, k = rope(q, pos, theta), rope(k, pos, theta)
        o = attention(q.reshape(-1, g, h // g, hd), k, v)
        o = o.reshape(-1, h, hd)
        x = x + mm("shk,hkd->sd", o, a["wo"], quant, (-2, -1), (0, 1))
        hn = rms(x, p["ln2"], eps)
        m = p["mlp"]
        gate = mm("sd,df->sf", hn, m["w_gate"], quant, -1, 0)
        up = mm("sd,df->sf", hn, m["w_up"], quant, -1, 0)
        x = x + mm("sf,fd->sd", jax.nn.silu(gate) * up, m["w_down"],
                   quant, -1, 0)
        return x, None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    xw = rms(x[want], params["ln_f"].astype(F32), eps)
    if "unembed" in params["embed"]:
        return mm("sd,dv->sv", xw, params["embed"]["unembed"].astype(F32),
                  quant, -1, 0)
    return mm("sd,vd->sv", xw, tok.astype(F32), quant, -1, -1)


#: the sizes ``_forward`` reads
_REF_KEYS = ("num_hidden_layers", "hidden_size", "num_attention_heads",
             "num_key_value_heads", "head_dim", "rms_norm_eps", "rope_theta",
             "qk_norm")


def logits(params, sizes: dict, tokens, want, quant=None):
    """float32 logits (len(want), vocab) of the sequence ``tokens`` at
    the positions ``want``."""
    sz = tuple((k, sizes.get(k)) for k in _REF_KEYS)
    return run(_forward, sz, params, tokens, want, quant)


# -- work counts -----------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Shapes:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    act_bytes: int = 2        # bf16 activations
    kv_bytes: int = 2         # pool element size

    @property
    def layer_matmul_params(self) -> int:
        """Weights one token multiplies per layer: q, k, v, o and the
        gated MLP (gate, up, down)."""
        d, h, g, hd = self.d, self.heads, self.kv_heads, self.head_dim
        return d * h * hd + 2 * d * g * hd + h * hd * d + 3 * d * self.d_ff

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


def shapes(s: dict, kv_bytes: int = 2) -> Shapes:
    return Shapes(layers=s["num_hidden_layers"], d=s["hidden_size"],
                  heads=s["num_attention_heads"],
                  kv_heads=s["num_key_value_heads"], head_dim=s["head_dim"],
                  d_ff=s["intermediate_size"], vocab=s["vocab_size"],
                  kv_bytes=kv_bytes)
