"""Random weights from the run's seed, made on the device in one jitted
call, in the dtype they are served in.

The tree has the layout the program's model expects (its shapes are
read from ``jax.eval_shape`` of the model's own initializer, which
allocates nothing).  Every matrix is normal with variance 1 / fan-in,
the token embedding is normal(0, 0.02), and RMSNorm gains, which the
program stores as offsets from 1, are normal(0, 0.1).  At these scales
attention is peaked and each layer's output is as large as its input,
so a served token depends on its context: a cache that loses a token,
or a norm left out, changes what is served, and the comparison with
the reference sees it.  (With every matrix at the 0.02 of common
initializers the logits barely depend on context, and a stale cache
goes unseen; with a token embedding of unit scale a tied head copies
the input token, and greedy decoding repeats one token forever.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: leaves that are RMSNorm gains (stored as offsets from 1)
NORM_LEAVES = frozenset({"ln1", "ln2", "ln_f", "q_norm", "k_norm"})
NORM_STD = 0.1
EMBED_STD = 0.02


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any whole-number seed (wider than 32 bits too)."""
    s = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.key(s & 0xFFFFFFFF), s >> 32)


def leaf_name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def _std(path, shape, fan_in) -> float:
    """``fan_in(name, shape)`` is the family's (``bench/families/``), given
    the leaf's shape after the leading layer axis, if stacked."""
    name = leaf_name(path)
    if name in NORM_LEAVES:
        return NORM_STD
    if name == "tok":
        return EMBED_STD
    stacked = any(getattr(p, "key", None) == "blocks" for p in path)
    return fan_in(name, shape[1:] if stacked else shape) ** -0.5


def make_params(init_fn, seed: int, fan_in):
    """Weights shaped like ``init_fn(key)``'s tree, drawn from ``seed``,
    each matrix scaled by the family's ``fan_in``."""
    abstract = jax.eval_shape(init_fn, jax.random.key(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)

    @jax.jit
    def build(key):
        vals = []
        for i, (path, leaf) in enumerate(leaves):
            std = _std(path, leaf.shape, fan_in)
            z = jax.random.normal(jax.random.fold_in(key, i), leaf.shape,
                                  jnp.float32)
            vals.append((z * std).astype(leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, vals)

    return build(seed_key(seed))
