"""The comparison that decides ``correct``.

After the window, a sample of the finished requests, drawn from the
seed and always holding the longest one, is run through the plain
reference once each: prompt plus served tokens.  At each served
position the reading is how far the served token's reference logit lies
below the reference's best there.  The number compared is the widest
such gap over the sample, in logits.

The reference is the ``logits`` of the configuration's family file
(``bench/families/<family>.py``).  The control (``control_gaps``) puts
the reference in the program's place at float8: at the same positions
of the same sequences it reads the gap of the token that the fp8
forward puts first.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Served:
    prompt: list
    tokens: list          # served output tokens, in order


def sample(done: list, seed: int, min_tokens: int,
           max_requests: int) -> list:
    """The longest finished request, then others in a seeded order until
    the sample serves ``min_tokens`` tokens or holds ``max_requests``."""
    if not done:
        return []
    rng = np.random.default_rng(int(seed) % (1 << 64) ^ 0x5EED)
    order = sorted(range(len(done)),
                   key=lambda i: -(len(done[i].prompt) + len(done[i].tokens)))
    rest = list(rng.permutation(order[1:]))
    out = [done[order[0]]]
    n = len(out[0].tokens)
    for i in rest:
        if n >= min_tokens or len(out) >= max_requests:
            break
        out.append(done[i])
        n += len(done[i].tokens)
    return out


def _positions(s: Served):
    seq = list(s.prompt) + list(s.tokens[:-1])
    want = np.arange(len(s.prompt) - 1, len(seq))
    return seq, want


def served_gaps(family, params, sizes: dict, items: list) -> np.ndarray:
    """Reference gap of every served token of ``items``."""
    out = []
    for s in items:
        seq, want = _positions(s)
        ref = family.logits(params, sizes, seq, want)
        tok = np.asarray(s.tokens)
        out.append(ref.max(axis=1) - ref[np.arange(len(tok)), tok])
    return np.concatenate(out) if out else np.zeros(0)


def control_gaps(family, params, sizes: dict, items: list,
                 quant: str = "fp8") -> np.ndarray:
    """Reference gap of the token the ``quant`` forward puts first, at
    every served position of ``items``."""
    out = []
    for s in items:
        seq, want = _positions(s)
        ref = family.logits(params, sizes, seq, want)
        low = family.logits(params, sizes, seq, want, quant=quant)
        top = low.argmax(axis=1)
        out.append(ref.max(axis=1) - ref[np.arange(len(top)), top])
    return np.concatenate(out) if out else np.zeros(0)
