"""Mamba-2 SSD (state-space duality) — Pallas TPU kernel.

The chunked SSD algorithm maps naturally onto the TPU grid: one program
instance per time chunk, sequential (the carried (H, N, P) state lives in
VMEM scratch across the grid sweep), quadratic-in-chunk work on the MXU
inside each instance.  The chunk length is the ``lws`` analogue over time
steps — resolved by ``models.ssm.plan_ssd_chunk`` (paper Eq. 1: temporal
loop per lane vs. number of sequential grid steps).

Layout notes (hardware adaptation): the (c, c) intra-chunk score matrix
and the (c, N/P) projections are MXU matmuls when c and the head dims are
128-aligned; heads are vmapped outside the kernel (they are embarrassingly
parallel and map to the mesh's model axis at the framework tier).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hw import TpuParams


def _ssd_kernel(x_ref, a_ref, b_ref, c_ref, o_ref, state_ref):
    """One chunk for ONE head group: x (c, P), a (c,), b/c (c, N)."""
    ci = pl.program_id(0)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[...].astype(jnp.float32)          # (c, P)
    a = a_ref[...].astype(jnp.float32)          # (c,)
    b = b_ref[...].astype(jnp.float32)          # (c, N)
    c = c_ref[...].astype(jnp.float32)          # (c, N)
    cl = x.shape[0]

    cum = jnp.cumsum(a)                          # (c,)
    total = cum[-1]
    # intra-chunk: dec(t, s) = exp(cum[t] - cum[s]) for s <= t
    dt = cum[:, None] - cum[None, :]
    mask = jax.lax.broadcasted_iota(jnp.int32, (cl, cl), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (cl, cl), 1)
    dec = jnp.where(mask, jnp.exp(dt), 0.0)
    scores = jnp.dot(c, b.T, preferred_element_type=jnp.float32) * dec
    y = jnp.dot(scores, x, preferred_element_type=jnp.float32)
    # carried-state contribution: y += (C * exp(cum)) @ state
    y += jnp.dot(c * jnp.exp(cum)[:, None], state_ref[...],
                 preferred_element_type=jnp.float32)
    # state' = exp(total) state + sum_s exp(total - cum[s]) B_s x_s
    w = jnp.exp(total - cum)[:, None]            # (c, 1)
    state_ref[...] = state_ref[...] * jnp.exp(total) + jnp.dot(
        (b * w).T, x, preferred_element_type=jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


def ssd_pallas_single(x, a, b, c, *, chunk: int, interpret: bool = False):
    """x (L, P), a (L,), b/c (L, N) — one head, L % chunk == 0."""
    l, p = x.shape
    n = b.shape[1]
    assert l % chunk == 0, (l, chunk)
    return pl.pallas_call(
        _ssd_kernel,
        out_shape=jax.ShapeDtypeStruct((l, p), x.dtype),
        grid=(l // chunk,),
        in_specs=[
            pl.BlockSpec((chunk, p), lambda i: (i, 0)),
            pl.BlockSpec((chunk,), lambda i: (i,)),
            pl.BlockSpec((chunk, n), lambda i: (i, 0)),
            pl.BlockSpec((chunk, n), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((chunk, p), lambda i: (i, 0)),
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(x, a, b, c)


def ssd_pallas(
    x: jax.Array,                 # (L, H, P)
    a: jax.Array,                 # (L, H) log-decay
    b: jax.Array,                 # (L, G, N)
    c: jax.Array,                 # (L, G, N)
    *,
    hw: TpuParams | None = None,
    chunk: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Multi-head SSD matching ``kernels.ref.ssd_chunked`` semantics."""
    l, h, p = x.shape
    g, n = b.shape[1], b.shape[2]
    rep = h // g
    bh = jnp.repeat(b, rep, axis=1)              # (L, H, N)
    ch = jnp.repeat(c, rep, axis=1)
    if chunk is None:
        from repro.models.ssm import plan_ssd_chunk
        chunk = plan_ssd_chunk(l, hw)
    chunk = min(chunk, l)
    while l % chunk:
        chunk //= 2
    fn = functools.partial(ssd_pallas_single, chunk=chunk,
                           interpret=interpret)
    # heads vmapped: (L,H,P) -> per-head (L,P)
    out = jax.vmap(fn, in_axes=(1, 1, 1, 1), out_axes=1)(x, a, bh, ch)
    return out
