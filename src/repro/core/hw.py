"""Hardware parameter introspection — the micro-architecture side of Eq. 1.

The paper reads Vortex device properties (cores, warps, threads) at runtime
and resolves the kernel mapping from them.  On TPU the analogous parameters
live at three tiers:

  tier 0 (mesh):   number of chips and their interconnect,
  tier 1 (core):   TensorCores per chip (program-level parallelism),
  tier 2 (lane):   VPU (8 sublanes x 128 lanes) and MXU (128x128) tiling.

``detect()`` queries ``jax.devices()`` at runtime (the paper's "evaluated at
runtime based on the hardware properties") and looks the chip up by its
``device_kind`` in a registry of known parts; an unknown TPU is an error.
A ``VortexParams`` model is kept as well so the paper's own
450-configuration sweep can be reproduced exactly (benchmarks/fig2_sweep).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

__all__ = [
    "TpuParams",
    "VortexParams",
    "TPU_REGISTRY",
    "TPU_DEVICE_KINDS",
    "detect",
    "hardware_parallelism",
]


@dataclasses.dataclass(frozen=True)
class TpuParams:
    """Micro-architecture parameters of one accelerator chip + its mesh.

    Bandwidths are bytes/s, compute is FLOP/s.  ``vmem_budget_bytes`` is the
    VMEM a single Pallas program may claim: the planners size blocks
    (double buffers and f32 temporaries included) against it, and every
    planned kernel declares it to the compiler as ``vmem_limit_bytes``.
    """

    name: str
    num_chips: int = 1                      # filled from the mesh at runtime
    cores_per_chip: int = 1                 # TensorCores ("cores" in Eq. 1)
    vpu_sublanes: int = 8                   # vector sublanes ("warps" analogue)
    vpu_lanes: int = 128                    # vector lanes ("threads" analogue)
    mxu_dim: int = 128                      # systolic array edge
    vmem_bytes: int = 128 * 1024 * 1024     # v5e: 128 MiB VMEM per core
    vmem_budget_bytes: int = 64 * 1024 * 1024
    hbm_bytes: int = 16 * 1024**3
    hbm_bw: float = 819e9                   # bytes/s
    peak_flops_bf16: float = 197e12
    ici_bw: float = 50e9                    # bytes/s per link
    ici_links: int = 4                      # v5e 2D torus: 4 links/chip
    clock_hz: float = 940e6
    launch_overhead_cycles: int = 500       # per-program dispatch cost model

    # ------------------------------------------------------------------ #
    @property
    def lane_tile(self) -> tuple[int, int]:
        """Minimum efficient vector tile (sublane, lane) = (8, 128)."""
        return (self.vpu_sublanes, self.vpu_lanes)

    @property
    def lane_parallelism(self) -> int:
        """Elements processed per VPU issue — tier-2 ``hp`` term."""
        return self.vpu_sublanes * self.vpu_lanes

    def hp(self, *, chips: Optional[int] = None) -> int:
        """Eq. 1's ``hp = cores x warps x threads`` generalized to TPU:

        ``hp = chips x cores_per_chip x sublanes x lanes``
        """
        c = self.num_chips if chips is None else chips
        return c * self.cores_per_chip * self.lane_parallelism

    def with_chips(self, num_chips: int) -> "TpuParams":
        return dataclasses.replace(self, num_chips=num_chips)


@dataclasses.dataclass(frozen=True)
class VortexParams:
    """The paper's native hardware model: ``<c>c<w>w<t>t`` configurations.

    Used by ``core.tracesim`` to reproduce the 450-configuration validation.
    Bandwidth/overhead defaults are calibrated to reproduce the three
    execution regimes of the paper's Fig. 1.
    """

    cores: int
    warps: int
    threads: int
    # one instruction issued per core per cycle (in-order scalar issue)
    issue_width: int = 1
    # global memory bytes per cycle for the whole device
    mem_bw_bytes_per_cycle: float = 16.0
    # round-trip memory latency in cycles; hidden only by warp interleaving
    mem_latency: int = 200
    # cycles to set up + tear down one kernel call (runtime dispatch, Fig. 1
    # "init"/"ret" sections between wavefronts).  Calibrated together with
    # mem_latency so the 450-config sweep reproduces the paper's aggregate
    # claims (naive 1.3x, fixed 3.7x, ~20x tails) — see EXPERIMENTS.md.
    call_overhead_cycles: int = 192

    @property
    def hp(self) -> int:
        """Eq. 1: hardware parallelism."""
        return self.cores * self.warps * self.threads

    @property
    def tag(self) -> str:
        return f"{self.cores}c{self.warps}w{self.threads}t"


# --------------------------------------------------------------------------- #
# Registry + runtime detection
# --------------------------------------------------------------------------- #

TPU_REGISTRY: dict[str, TpuParams] = {
    "tpu_v5e": TpuParams(name="tpu_v5e"),
    "tpu_v4": TpuParams(
        name="tpu_v4",
        cores_per_chip=2,
        vmem_bytes=128 * 1024 * 1024,
        hbm_bytes=32 * 1024**3,
        hbm_bw=1200e9,
        peak_flops_bf16=275e12,
        ici_bw=100e9,
        ici_links=6,
    ),
    # CPU stand-in so the whole stack runs (and is tested) in this container.
    # Lane geometry matches TPU so block planning is identical; budgets are
    # scaled down so interpret-mode kernels stay fast.
    "cpu_sim": TpuParams(
        name="cpu_sim",
        vmem_bytes=16 * 1024 * 1024,
        vmem_budget_bytes=8 * 1024 * 1024,
        hbm_bytes=8 * 1024**3,
        hbm_bw=50e9,
        peak_flops_bf16=1e12,
        ici_bw=10e9,
    ),
}


#: ``jax.Device.device_kind`` of each registered TPU -> its registry entry
TPU_DEVICE_KINDS: dict[str, str] = {
    "TPU v5 lite": "tpu_v5e",
    "TPU v4": "tpu_v4",
}


def detect(num_chips: Optional[int] = None) -> TpuParams:
    """Runtime hardware introspection (paper §2: "evaluated at runtime
    based on the hardware properties").

    Maps ``jax.devices()[0].device_kind`` onto the registry through
    ``TPU_DEVICE_KINDS``; a TPU kind not listed there raises
    ``ValueError`` (its parameters would be a guess).  Non-TPU platforms
    get the ``cpu_sim`` stand-in.
    """
    import jax

    devs = jax.devices()
    n = num_chips if num_chips is not None else len(devs)
    if devs[0].platform != "tpu":
        return TPU_REGISTRY["cpu_sim"].with_chips(n)
    kind = devs[0].device_kind
    if kind not in TPU_DEVICE_KINDS:
        raise ValueError(f"unknown TPU device_kind {kind!r}: add its "
                         f"parameters to TPU_REGISTRY and TPU_DEVICE_KINDS")
    return TPU_REGISTRY[TPU_DEVICE_KINDS[kind]].with_chips(n)


def hardware_parallelism(hw: TpuParams) -> int:
    """Module-level convenience mirroring Eq. 1's ``hp``."""
    return hw.hp()


def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def round_up(x: int, quantum: int) -> int:
    return ceil_div(x, quantum) * quantum


def round_down_pow2(x: int) -> int:
    return 1 if x <= 1 else 2 ** int(math.log2(x))
