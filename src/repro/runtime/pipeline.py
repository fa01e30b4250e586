"""Pipeline parallelism over the `pod` axis (GPipe schedule).

Cross-pod ICI/DCN links are the slowest tier of a multi-pod machine, so
the natural multi-pod mapping for very deep models is one pipeline STAGE
per pod: the only cross-pod traffic becomes one (microbatch, seq, d_model)
activation per pipeline tick instead of every gradient all-reduce.

Implementation: ``shard_map`` over the stage axis; each rank holds its
stage's layer stack; microbatches stream through a lax.scan of
``n_micro + n_stages - 1`` ticks with ``ppermute`` handoffs (the classic
GPipe bubble).  The whole schedule is differentiable — ``jax.grad``
through ``pipeline_apply`` yields the standard GPipe backward (reverse
bubble), so it composes with the existing train step machinery.

Eq. 1 shows up once more: the microbatch count trades bubble fraction
(S-1)/(T+S-1) against per-tick activation memory — ``plan_pipeline``
resolves it from the stage count and the HBM budget.

Scope: stages must be shape-preserving (residual-stream blocks); embed /
unembed run outside the pipeline (replicated — cheap relative to blocks).
Tested for exact fwd/bwd equivalence vs the sequential stack in
``tests/test_pipeline.py`` (subprocess, real 2-device mesh).
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.hw import ceil_div

PyTree = Any


def split_stages(stacked_params: PyTree, n_stages: int) -> PyTree:
    """(L, ...) leaves -> (S, L/S, ...): one sub-stack per stage."""
    def sp(a):
        l = a.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return a.reshape((n_stages, l // n_stages) + a.shape[1:])
    return jax.tree.map(sp, stacked_params)


def plan_pipeline(per_device_batch: int, n_stages: int,
                  act_bytes_per_seq: float, hbm_budget: float) -> int:
    """Microbatch count for the pipeline: enough microbatches to keep the
    bubble small (>= 4x stages is the GPipe rule of thumb) AND fit the
    in-flight activations."""
    by_bubble = min(per_device_batch, 4 * n_stages)
    fit = max(1, int(hbm_budget // max(act_bytes_per_seq, 1.0)))
    n = max(by_bubble, ceil_div(per_device_batch, fit))
    while per_device_batch % n:
        n += 1
    return min(n, per_device_batch)


def pipeline_apply(
    stage_fn: Callable[[PyTree, jax.Array], jax.Array],
    stage_params: PyTree,          # leaves (S, L/S, ...) — stage-sharded
    x: jax.Array,                  # (n_micro, mb, seq, d) — full input
    *,
    mesh: Mesh,
    axis: str = "pod",
) -> jax.Array:
    """Run the GPipe schedule; returns (n_micro, mb, seq, d) outputs.

    ``stage_fn(params_one_stage, x_mb) -> y_mb`` must be shape-preserving.
    """
    n_stages = mesh.shape[axis]
    n_micro = x.shape[0]
    t_total = n_micro + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def ranked(params, xs):
        idx = jax.lax.axis_index(axis)
        # shard_map gives this rank its own (1, L/S, ...) slice; drop the
        # leading stage axis
        params = jax.tree.map(lambda a: a[0], params)
        xs = xs[0] if xs.ndim > 4 else xs          # (n_micro, mb, s, d)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t while t < n_micro
            feed = jax.lax.dynamic_index_in_dim(
                xs, jnp.minimum(t, n_micro - 1), keepdims=False)
            x_in = jnp.where(idx == 0, feed, buf)
            y = stage_fn(params, x_in)
            active = (t - idx >= 0) & (t - idx < n_micro)
            y = jnp.where(active, y, buf)
            # the last stage records its finished microbatch
            out_t = t - (n_stages - 1)
            record = (idx == n_stages - 1) & (out_t >= 0)
            outs = jax.lax.cond(
                record,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.maximum(out_t, 0), 0),
                lambda o: o, outs)
            buf = jax.lax.ppermute(y, axis, perm)
            return (buf, outs), None

        buf0 = jnp.zeros_like(xs[0])
        outs0 = jnp.zeros_like(xs)
        (_, outs), _ = jax.lax.scan(tick, (buf0, outs0),
                                    jnp.arange(t_total))
        # broadcast the last stage's outputs to every rank
        outs = jax.lax.psum(
            jnp.where(idx == n_stages - 1, outs, jnp.zeros_like(outs)),
            axis)
        return outs[None]

    fn = jax.shard_map(
        ranked, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(axis), stage_params), P()),
        out_specs=P(axis),
        check_vma=False,
    )
    out = fn(stage_params, x)      # (S, n_micro, mb, s, d), S identical copies
    return out[0]


def sequential_apply(stage_fn, stage_params, x):
    """Reference: run the stages back-to-back on one device."""
    n_stages = jax.tree.leaves(stage_params)[0].shape[0]

    def body(xc, s):
        p = jax.tree.map(lambda a: a[s], stage_params)
        return stage_fn(p, xc), None

    def per_micro(xm):
        y, _ = jax.lax.scan(body, xm, jnp.arange(n_stages))
        return y

    return jax.vmap(per_micro)(x)
