"""Tiled MXU matmul with runtime-resolved (bm, bn, bk) blocks.

Grid is (m/bm, n/bn, k/bk) with the reduction dimension innermost
(sequential on TPU); partial products accumulate in an f32 VMEM scratch
and spill to the output block once per (i, j) tile — the canonical TPU
matmul schedule.  The block shapes are the ``lws`` analogue, resolved by
``core.mapper.plan_matmul_blocks`` from the detected hardware.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hw import TpuParams, round_up
from repro.core.mapper import MappingPolicy, MatmulPlan, plan_matmul_blocks


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...].astype(jnp.float32),
        b_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ki == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul_pallas(
    a: jax.Array,
    b: jax.Array,
    *,
    hw: TpuParams,
    policy: MappingPolicy = MappingPolicy.AUTO,
    plan: MatmulPlan | None = None,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """C[m,n] = A[m,k] @ B[k,n] with mapper-chosen tiling."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    out_dtype = out_dtype or a.dtype
    if plan is None:
        plan = plan_matmul_blocks(m, n, k, hw, policy,
                                  dtype_bytes=a.dtype.itemsize)
    bm, bn, bk = plan.bm, plan.bn, plan.bk
    mp, np_, kp = round_up(m, bm), round_up(n, bn), round_up(k, bk)
    if (mp, kp) != (m, k):
        a = jnp.pad(a, ((0, mp - m), (0, kp - k)))
    if (kp, np_) != (k, n):
        b = jnp.pad(b, ((0, kp - k), (0, np_ - n)))
    out = pl.pallas_call(
        _matmul_kernel,
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        grid=(mp // bm, np_ // bn, kp // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=hw.vmem_budget_bytes,
        ),
        interpret=interpret,
    )(a, b)
    return out[:m, :n] if (mp, np_) != (m, n) else out


@functools.partial(jax.jit, static_argnames=("hw", "policy", "interpret"))
def matmul(a, b, hw, policy=MappingPolicy.AUTO, interpret=False):
    return matmul_pallas(a, b, hw=hw, policy=policy, interpret=interpret)
