"""Separable gaussian blur — 2-pass stencil with explicit halo exchange.

The paper's gaussian blur shows "atypical trends" (§3) because stencils
reuse neighbour data; on TPU that reuse is explicit: the column pass needs
``halo`` rows from the neighbouring blocks, which we express as three
shifted BlockSpecs over the same operand (prev / current / next row-block)
— the TPU-idiomatic halo exchange (no shared-memory staging as on GPU).

Row pass needs no halo (full width resident per block).  Block row count is
the ``lws`` analogue, resolved by the runtime planner.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hw import TpuParams, round_up
from repro.core.mapper import MappingPolicy, resolve_lws
from repro.kernels.ref import gaussian_kernel_1d


def plan_stencil_rows(h: int, w: int, hw: TpuParams, policy: MappingPolicy,
                      dtype_bytes: int, halo: int) -> int:
    if policy is MappingPolicy.NAIVE:
        rows = 8
    elif policy is MappingPolicy.FIXED:
        rows = 128
    else:
        rows = round_up(resolve_lws(h, hw.cores_per_chip), 8)
        cap = max(8, (hw.vmem_budget_bytes // (4 * w * dtype_bytes)) // 8 * 8)
        rows = min(rows, cap)
    return max(rows, round_up(halo, 8))


def _row_pass_kernel(x_ref, o_ref, *, taps: tuple[float, ...]):
    """Convolve along the width (axis 1); zero 'same' padding via shifts."""
    x = x_ref[...].astype(jnp.float32)
    half = (len(taps) - 1) // 2
    acc = jnp.zeros_like(x)
    w = x.shape[1]
    for t, coef in enumerate(taps):
        off = t - half
        # shift along axis 1 with zero fill
        if off == 0:
            sh = x
        elif off > 0:
            sh = jnp.pad(x[:, off:], ((0, 0), (0, off)))
        else:
            sh = jnp.pad(x[:, :w + off], ((0, 0), (-off, 0)))
        acc += coef * sh
    o_ref[...] = acc.astype(o_ref.dtype)


def _col_pass_kernel(prev_ref, cur_ref, nxt_ref, o_ref,
                     *, taps: tuple[float, ...], halo: int):
    i = pl.program_id(0)
    n = pl.num_programs(0)
    prev = prev_ref[...].astype(jnp.float32)
    cur = cur_ref[...].astype(jnp.float32)
    nxt = nxt_ref[...].astype(jnp.float32)
    # boundary blocks: the clamped neighbour block is wrong data; zero it
    prev = jnp.where(i == 0, 0.0, prev)
    nxt = jnp.where(i == n - 1, 0.0, nxt)
    ext = jnp.concatenate([prev[-halo:], cur, nxt[:halo]], axis=0)
    br = cur.shape[0]
    acc = jnp.zeros_like(cur)
    for t, coef in enumerate(taps):
        acc += coef * ext[t:t + br]
    o_ref[...] = acc.astype(o_ref.dtype)


def gaussian_blur_pallas(
    img: jax.Array,
    *,
    hw: TpuParams,
    ksize: int = 5,
    sigma: float = 1.0,
    policy: MappingPolicy = MappingPolicy.AUTO,
    block_rows: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """img: (H, W).  Returns blurred image, zero-padded 'same' semantics."""
    h, w = img.shape
    halo = (ksize - 1) // 2
    taps = tuple(float(t) for t in np.asarray(gaussian_kernel_1d(ksize, sigma)))
    if block_rows is None:
        block_rows = plan_stencil_rows(h, w, hw, policy, img.dtype.itemsize, halo)
    hp_ = round_up(h, block_rows)
    x = jnp.pad(img, ((0, hp_ - h), (0, 0))) if hp_ != h else img
    grid = (hp_ // block_rows,)
    spec = pl.BlockSpec((block_rows, w), lambda i: (i, 0))

    rows = pl.pallas_call(
        functools.partial(_row_pass_kernel, taps=taps),
        out_shape=jax.ShapeDtypeStruct((hp_, w), img.dtype),
        grid=grid, in_specs=[spec], out_specs=spec,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=hw.vmem_budget_bytes),
        interpret=interpret,
    )(x)

    nb = grid[0]
    out = pl.pallas_call(
        functools.partial(_col_pass_kernel, taps=taps, halo=halo),
        out_shape=jax.ShapeDtypeStruct((hp_, w), img.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, w),
                         lambda i: (jnp.maximum(i - 1, 0), 0)),
            pl.BlockSpec((block_rows, w), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, w),
                         lambda i: (jnp.minimum(i + 1, nb - 1), 0)),
        ],
        out_specs=spec,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=hw.vmem_budget_bytes),
        interpret=interpret,
    )(rows, rows, rows)
    return out[:h] if hp_ != h else out
