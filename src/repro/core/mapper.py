"""Runtime hardware-aware workload mapping — the paper's core contribution.

Implements Eq. 1 (``lws = gws / hp``) and its TPU generalization at the
three hardware tiers (mesh / core-grid / lane-tile), plus the two reference
policies the paper compares against:

  * ``NAIVE`` — the ``lws=1`` mapping: never loop temporally inside one lane,
    spawn maximal software parallelism (maximal grid, minimal blocks);
  * ``FIXED`` — the ``lws=32`` mapping: one constant block size independent
    of both workload and hardware;
  * ``AUTO``  — Eq. 1 resolved at runtime from the detected hardware
    parameters, then rounded to the lane-tile quanta and clamped by the
    VMEM budget;
  * ``TUNED`` — the AUTO seed refined by the ``repro.tuner`` subsystem:
    ``tuner.dispatch`` hill-climbs the cost model around the Eq. 1 seed
    (the paper's §3 "small benefits" observation) and memoizes the winner
    in a persistent hardware-keyed cache.  Inside this module TUNED plans
    identically to AUTO — the refinement happens in the dispatch layer.

All planners are pure functions of (workload, hardware, policy): they can be
called at trace time inside ``jax.jit`` staging, which is the TPU equivalent
of the paper's "evaluated at runtime ... without being explicitly specified
by the programmer".  The ``*_plan_for_block*`` helpers rebuild a full plan
from just the tuned decision variables (block sizes), so cached tuning
entries only need to persist those.
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Optional

from repro.core.hw import TpuParams, ceil_div, round_up
from repro.core.workload import Workload

__all__ = [
    "MappingPolicy",
    "Regime",
    "resolve_lws",
    "classify_regime",
    "BlockPlan",
    "MatmulPlan",
    "AttentionPlan",
    "MeshPlan",
    "plan_vector_blocks",
    "plan_matmul_blocks",
    "plan_attention_blocks",
    "plan_microbatch",
    "plan_moe_capacity",
    "vector_plan_for_block",
    "matmul_plan_for_blocks",
    "attention_plan_for_blocks",
]

FIXED_LWS = 32          # the paper's fixed baseline
FIXED_BLOCK_1D = 128    # hardware-legal translation of lws=32 to a lane tile
FIXED_BLOCK_MM = 128    # fixed square matmul tile


class MappingPolicy(str, enum.Enum):
    NAIVE = "naive"
    FIXED = "fixed"
    AUTO = "auto"
    TUNED = "tuned"


class Regime(str, enum.Enum):
    """The three scenarios of the paper's Fig. 1."""

    OVERSUBSCRIBED = "oversubscribed"    # lws < gws/hp: multiple kernel calls
    EXACT = "exact"                      # lws = gws/hp: single full call
    UNDERSUBSCRIBED = "undersubscribed"  # lws > gws/hp: idle hardware


def resolve_lws(gws: int, hp: int) -> int:
    """Eq. 1: ``lws = gws / hp`` — resolves to 1 when ``hp`` exceeds ``gws``
    (paper §3: "when the hardware parallelism hp exceeds the gws ... Eq. 1
    resolves to lws=1")."""
    return max(1, ceil_div(gws, hp))


def classify_regime(lws: int, gws: int, hp: int) -> Regime:
    needed_lanes = ceil_div(gws, lws)
    if needed_lanes > hp:
        return Regime.OVERSUBSCRIBED
    if needed_lanes == hp or gws == lws * hp:
        return Regime.EXACT
    return Regime.UNDERSUBSCRIBED


# --------------------------------------------------------------------------- #
# Tier 1+2: Pallas block/grid planning
# --------------------------------------------------------------------------- #


@dataclasses.dataclass(frozen=True)
class BlockPlan:
    """Mapping decision for a 1D/elementwise Pallas kernel.

    ``block_elems`` is the ``lws`` analogue: the number of elements one
    program instance loops over temporally.  ``grid`` is the number of
    program instances.  ``sequential_rounds`` counts how many waves of
    programs the hardware needs (>1 == the paper's "multiple kernel calls"
    regime).
    """

    policy: MappingPolicy
    block_elems: int
    grid: int
    padded_gws: int
    sequential_rounds: int
    utilization: float
    regime: Regime
    vmem_bytes: int

    @property
    def block_shape(self) -> tuple[int, ...]:
        return (self.block_elems,)


def _lane_quantum(hw: TpuParams) -> int:
    return hw.vpu_sublanes * hw.vpu_lanes  # 1024 elements


def plan_vector_blocks(
    w: Workload,
    hw: TpuParams,
    policy: MappingPolicy = MappingPolicy.AUTO,
    n_streams: int = 3,
) -> BlockPlan:
    """Map an elementwise kernel of ``gws`` elements onto one chip.

    ``n_streams`` is the number of same-size arrays held in VMEM at once
    (inputs + outputs) for the VMEM clamp.
    """
    q = _lane_quantum(hw)
    hp_programs = hw.cores_per_chip  # concurrently resident programs
    vmem_cap = hw.vmem_budget_bytes // (2 * n_streams * w.dtype_bytes)
    vmem_cap = max(q, (vmem_cap // q) * q)

    if policy is MappingPolicy.NAIVE:
        block = q                                   # minimal legal block
    elif policy is MappingPolicy.FIXED:
        block = FIXED_BLOCK_1D * FIXED_LWS          # constant, hw-agnostic
    else:
        # Eq. 1 at tier 1/2 (AUTO and the TUNED seed): each resident program
        # loops gws / hp elements, where hp counts resident programs x lane
        # parallelism.
        lws = resolve_lws(w.gws, hp_programs * q)
        block = round_up(lws, 1) * q                # lws lane-tiles per program
        block = min(block, vmem_cap)
    return vector_plan_for_block(w, hw, block, policy, n_streams=n_streams)


def vector_plan_for_block(
    w: Workload,
    hw: TpuParams,
    block: int,
    policy: MappingPolicy = MappingPolicy.TUNED,
    n_streams: int = 3,
) -> BlockPlan:
    """Build the full ``BlockPlan`` from one decision variable (``block``).

    Legalizes the candidate (lane-quantum rounding, gws clamp) and derives
    grid / rounds / utilization — the single source of truth shared by the
    policy planners above and the tuner's candidate evaluation, so a cached
    tuning entry only needs to persist ``block_elems``.
    """
    q = _lane_quantum(hw)
    hp_programs = hw.cores_per_chip
    block = max(q, (block // q) * q)
    block = min(block, round_up(w.gws, q))
    padded = round_up(w.gws, block)
    grid = padded // block
    rounds = ceil_div(grid, hp_programs)
    # Utilization: real elements / lane-slots claimed (padding + idle
    # programs in the final round both count as waste).
    util = w.gws / (rounds * hp_programs * block)
    lws_eff = block // q
    return BlockPlan(
        policy=policy,
        block_elems=block,
        grid=grid,
        padded_gws=padded,
        sequential_rounds=rounds,
        utilization=util,
        regime=classify_regime(lws_eff, ceil_div(w.gws, q), hp_programs),
        # every stream's block is double-buffered by the Pallas pipeline
        vmem_bytes=2 * block * w.dtype_bytes * n_streams,
    )


@dataclasses.dataclass(frozen=True)
class MatmulPlan:
    policy: MappingPolicy
    bm: int
    bn: int
    bk: int
    grid: tuple[int, int, int]       # (m/bm, n/bn, k/bk)
    utilization: float               # MXU tile occupancy incl. padding
    vmem_bytes: int
    regime: Regime


def _matmul_vmem_bytes(bm: int, bn: int, bk: int, dtype_bytes: int) -> int:
    """VMEM one ``kernels.matmul`` program claims: double-buffered A, B
    and C blocks, plus the f32 accumulator scratch, the f32 casts of the
    A and B blocks and the f32 product tile the kernel body creates."""
    return (2 * (bm * bk + bk * bn + bm * bn) * dtype_bytes
            + (bm * bk + bk * bn + 2 * bm * bn) * 4)


def plan_matmul_blocks(
    m: int,
    n: int,
    k: int,
    hw: TpuParams,
    policy: MappingPolicy = MappingPolicy.AUTO,
    dtype_bytes: int = 2,
) -> MatmulPlan:
    """Map C[m,n] += A[m,k]B[k,n] onto MXU tiles.

    The ``lws`` analogue is the (bm, bn) output tile one program owns; the
    reduction is looped over ``bk`` chunks inside the program (temporal).
    AUTO solves Eq. 1 over output tiles: tiles_total = (m/128)(n/128),
    per-program tiles = tiles_total / cores, then factorizes into bm x bn
    favouring square-ish blocks and clamps by VMEM (``_matmul_vmem_bytes``).
    """
    t = hw.mxu_dim
    mt, nt = ceil_div(m, t), ceil_div(n, t)

    def vmem(bm: int, bn: int, bk: int) -> int:
        return _matmul_vmem_bytes(bm, bn, bk, dtype_bytes)

    if policy is MappingPolicy.NAIVE:
        bm, bn = min(t, round_up(m, 8)), min(t, round_up(n, t))
        bk = min(k, 512)
    elif policy is MappingPolicy.FIXED:
        bm = bn = FIXED_BLOCK_MM
        bk = min(k, FIXED_BLOCK_MM * 4)
    else:
        tiles_per_prog = resolve_lws(mt * nt, hw.cores_per_chip)
        # favour wide bn (lane-contiguous) then tall bm
        bn_tiles = min(nt, tiles_per_prog)
        bm_tiles = min(mt, max(1, tiles_per_prog // bn_tiles))
        bm, bn = bm_tiles * t, bn_tiles * t
        bk = min(round_up(k, t), 2048)
        while vmem(bm, bn, bk) > hw.vmem_budget_bytes and bk > t:
            bk //= 2
        while vmem(bm, bn, bk) > hw.vmem_budget_bytes and (bm > t or bn > t):
            if bm >= bn and bm > t:
                bm //= 2
            elif bn > t:
                bn //= 2
        bm, bn = max(t, bm), max(t, bn)
    return matmul_plan_for_blocks(m, n, k, hw, bm, bn, bk, policy,
                                  dtype_bytes=dtype_bytes)


def matmul_plan_for_blocks(
    m: int,
    n: int,
    k: int,
    hw: TpuParams,
    bm: int,
    bn: int,
    bk: int,
    policy: MappingPolicy = MappingPolicy.TUNED,
    dtype_bytes: int = 2,
) -> MatmulPlan:
    """Build the full ``MatmulPlan`` from the (bm, bn, bk) decision —
    shared by ``plan_matmul_blocks`` and the tuner (cached entries persist
    only the three block sizes)."""
    t = hw.mxu_dim
    mt, nt = ceil_div(m, t), ceil_div(n, t)
    # shape clamps only (policy branches/tuner candidates own the lower
    # bounds); the max(1, ...) floor just guards degenerate cached values
    bm = min(max(1, bm), round_up(m, 8))
    bn = min(max(1, bn), round_up(n, t))
    bk = min(max(1, bk), round_up(k, t))
    grid = (ceil_div(m, bm), ceil_div(n, bn), ceil_div(k, bk))
    padded = grid[0] * bm * grid[1] * bn
    util = (m * n) / padded
    vmem = _matmul_vmem_bytes(bm, bn, bk, dtype_bytes)
    lws_tiles = (bm // min(bm, t)) * max(bn // t, 1)
    return MatmulPlan(
        policy=policy, bm=bm, bn=bn, bk=bk, grid=grid,
        utilization=util, vmem_bytes=vmem,
        regime=classify_regime(lws_tiles, mt * nt, hw.cores_per_chip),
    )


@dataclasses.dataclass(frozen=True)
class AttentionPlan:
    policy: MappingPolicy
    block_q: int
    block_k: int
    grid_q: int
    vmem_bytes: int


def _attention_vmem_bytes(bq: int, bk: int, head_dim: int,
                         dtype_bytes: int) -> int:
    """VMEM one ``kernels.flash_attention`` program claims (head_dim
    lane-padded to 128): double-buffered q, k, v and o blocks, the f32
    running stats and accumulator, the f32 casts of q, k and v, and the
    f32 score and probability tiles."""
    hd = max(head_dim, 128)
    return (2 * (2 * bq * hd + 2 * bk * hd) * dtype_bytes
            + (2 * bq * hd + 2 * bk * hd + 2 * bq * bk + 2 * bq) * 4)


def plan_attention_blocks(
    seq_q: int,
    seq_k: int,
    head_dim: int,
    hw: TpuParams,
    policy: MappingPolicy = MappingPolicy.AUTO,
    dtype_bytes: int = 2,
) -> AttentionPlan:
    """Flash-attention tiling: block_q rows resident, loop seq_k in block_k
    chunks (the temporal ``lws`` loop)."""
    def vmem(bq: int, bk: int) -> int:
        return _attention_vmem_bytes(bq, bk, head_dim, dtype_bytes)

    if policy is MappingPolicy.NAIVE:
        bq, bk = 8, 128
    elif policy is MappingPolicy.FIXED:
        bq, bk = 128, 128
    else:
        # Eq. 1 over q-rows: rows per program = seq_q / cores, tile-rounded.
        bq = min(round_up(resolve_lws(seq_q, hw.cores_per_chip), 128), 1024)
        bk = min(round_up(seq_k, 128), 1024)
        while vmem(bq, bk) > hw.vmem_budget_bytes and bk > 128:
            bk //= 2
        while vmem(bq, bk) > hw.vmem_budget_bytes and bq > 128:
            bq //= 2
    return attention_plan_for_blocks(seq_q, seq_k, head_dim, hw, bq, bk,
                                     policy, dtype_bytes=dtype_bytes)


def attention_plan_for_blocks(
    seq_q: int,
    seq_k: int,
    head_dim: int,
    hw: TpuParams,
    bq: int,
    bk: int,
    policy: MappingPolicy = MappingPolicy.TUNED,
    dtype_bytes: int = 2,
) -> AttentionPlan:
    """Build the full ``AttentionPlan`` from the (block_q, block_k)
    decision — shared by ``plan_attention_blocks`` and the tuner."""
    del hw  # legalization is shape-driven; kept for signature symmetry
    bq = min(max(8, bq // 8 * 8), round_up(seq_q, 8))
    bk = min(max(128, bk // 128 * 128), round_up(seq_k, 128))
    vmem = _attention_vmem_bytes(bq, bk, head_dim, dtype_bytes)
    return AttentionPlan(
        policy=policy, block_q=bq, block_k=bk,
        grid_q=ceil_div(seq_q, bq), vmem_bytes=vmem,
    )


# --------------------------------------------------------------------------- #
# Tier 0: mesh-level mapping (per-device batch + microbatching)
# --------------------------------------------------------------------------- #


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    """Eq. 1 at the mesh tier.

    ``per_device_batch`` is ``gws/hp`` with gws = global batch and hp = the
    data-parallel world size.  ``num_microbatches`` > 1 is the productive
    reuse of the paper's "multiple kernel calls" regime: when the activation
    working set exceeds the HBM budget we *deliberately* oversubscribe
    temporally (gradient accumulation) instead of failing.
    """

    global_batch: int
    data_parallel: int
    per_device_batch: int
    num_microbatches: int
    microbatch_per_device: int
    padding: int
    regime: Regime
    activation_bytes_per_device: int
    # v2 collective schedule: accumulate grads locally across microbatches,
    # reduce once at the end (vs. naive per-microbatch all-reduce).
    reduce_once: bool = True


def plan_microbatch(
    global_batch: int,
    data_parallel: int,
    activation_bytes_per_seq: float,
    hbm_budget_bytes: float,
    policy: MappingPolicy = MappingPolicy.AUTO,
) -> MeshPlan:
    """Resolve per-device batch and microbatch count at runtime.

    activation_bytes_per_seq: bytes of live activations one sequence
    contributes on one device under the current remat policy.
    """
    padded = round_up(global_batch, data_parallel)
    pdb = padded // data_parallel
    if policy is MappingPolicy.NAIVE:
        micro = pdb  # microbatch of 1 sequence: lws=1 analogue
    elif policy is MappingPolicy.FIXED:
        micro = max(1, ceil_div(pdb, FIXED_LWS))  # fixed 32-seq microbatches
    else:
        fit = max(1, int(hbm_budget_bytes // max(activation_bytes_per_seq, 1.0)))
        micro = ceil_div(pdb, fit)
        while pdb % micro:
            micro += 1
    micro = max(1, min(micro, pdb))
    while pdb % micro:
        micro += 1
    mpd = pdb // micro
    regime = (
        Regime.OVERSUBSCRIBED if micro > 1
        else (Regime.EXACT if padded == global_batch else Regime.UNDERSUBSCRIBED)
    )
    return MeshPlan(
        global_batch=global_batch,
        data_parallel=data_parallel,
        per_device_batch=pdb,
        num_microbatches=micro,
        microbatch_per_device=mpd,
        padding=padded - global_batch,
        regime=regime,
        activation_bytes_per_device=int(mpd * activation_bytes_per_seq),
    )


def plan_moe_capacity(
    tokens: int,
    num_experts: int,
    top_k: int,
    ep_size: int,
    policy: MappingPolicy = MappingPolicy.AUTO,
    slack: float = 1.25,
) -> int:
    """Expert capacity = Eq. 1 over routed token-slots.

    gws = tokens * top_k routed slots; hp = num_experts "lanes"; lws = the
    per-expert capacity.  AUTO adds the standard load-imbalance slack and
    rounds to the lane quantum (128) so the expert matmuls stay MXU-aligned.
    """
    ideal = ceil_div(tokens * top_k, num_experts)
    if policy is MappingPolicy.NAIVE:
        cap = ideal  # no slack: drops under imbalance
    elif policy is MappingPolicy.FIXED:
        cap = FIXED_LWS * 4
    else:
        cap = int(ideal * slack)
    cap = max(8, round_up(cap, 8))
    del ep_size
    return cap
