"""Three-term roofline extraction from compiled XLA artifacts.

For every dry-run cell we derive (TPU v5e constants):

  compute term    = HLO_FLOPs / (chips x 197 TFLOP/s)
  memory term     = HLO_bytes / (chips x 819 GB/s)
  collective term = collective_bytes / (chips x 4 links x 50 GB/s)

``cost_analysis()`` supplies per-device FLOPs and bytes for the SPMD
program.  Collective bytes are NOT in cost_analysis: we parse the
post-SPMD HLO text and sum the traffic of every all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute, using per-device link
traffic models (ring algorithms):

  all-gather:        (g-1)/g x result_bytes        received per device
  reduce-scatter:    (g-1)/g x operand_bytes       sent per device
  all-reduce:        2 x (g-1)/g x operand_bytes   (RS + AG)
  all-to-all:        (g-1)/g x operand_bytes
  collective-permute: operand_bytes

where g is the replica-group size parsed from the op.
"""

from __future__ import annotations

import dataclasses
import math
import re
from typing import Any, Optional

__all__ = [
    "HwConstants",
    "TPU_V5E",
    "CollectiveStats",
    "collective_stats_from_hlo",
    "RooflineReport",
    "roofline_from_compiled",
    "kernel_roofline_seconds",
    "model_flops_per_step",
]


@dataclasses.dataclass(frozen=True)
class HwConstants:
    name: str
    peak_flops: float          # per chip, bf16
    hbm_bw: float              # bytes/s per chip
    link_bw: float             # bytes/s per ICI link
    links_per_chip: int


TPU_V5E = HwConstants(
    name="tpu_v5e", peak_flops=197e12, hbm_bw=819e9,
    link_bw=50e9, links_per_chip=4,
)

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1,
    "f8e5m2": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

# `bf16[256,4096]{1,0}` or `f32[]`
_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")
_COLLECTIVE_KINDS = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)


def _shape_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def _group_size(line: str, default: int) -> int:
    """Parse replica group size from replica_groups={{0,1,...},{...}} or
    the newer iota syntax [N,G]<=[...]"""
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)
    if m:
        return int(m.group(2))
    m = re.search(r"replica_groups=\{\{([0-9, ]+)\}", line)
    if m:
        return len(m.group(1).split(","))
    return default


@dataclasses.dataclass
class CollectiveStats:
    """Per-device collective traffic (bytes) by op kind."""

    bytes_by_kind: dict[str, float] = dataclasses.field(default_factory=dict)
    count_by_kind: dict[str, int] = dataclasses.field(default_factory=dict)

    @property
    def total_bytes(self) -> float:
        return sum(self.bytes_by_kind.values())

    @property
    def total_count(self) -> int:
        return sum(self.count_by_kind.values())


def collective_stats_from_hlo(hlo_text: str, world: int) -> CollectiveStats:
    """Sum per-device link traffic of every collective in the HLO module."""
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        s = line.strip()
        # result shape appears before `= kind(`; match ` = <shapes> kind(`
        m = re.search(r"=\s+(.+?)\s+(" + "|".join(_COLLECTIVE_KINDS) + r")(-start|-done)?\(", s)
        if not m:
            continue
        if m.group(3) == "-done":      # -done carries no new traffic
            continue
        result_sig, kind = m.group(1), m.group(2)
        shapes = _SHAPE_RE.findall(result_sig)
        result_bytes = sum(_shape_bytes(d, dims) for d, dims in shapes)
        # operand shapes are inside the parens
        args = s[m.end():]
        operand_bytes = sum(
            _shape_bytes(d, dims) for d, dims in _SHAPE_RE.findall(args)
        )
        g = _group_size(s, world)
        frac = (g - 1) / g if g > 1 else 0.0
        if kind == "all-gather":
            traffic = frac * result_bytes
        elif kind == "reduce-scatter":
            traffic = frac * operand_bytes
        elif kind == "all-reduce":
            traffic = 2.0 * frac * operand_bytes
        elif kind == "all-to-all":
            traffic = frac * operand_bytes
        else:  # collective-permute
            traffic = float(operand_bytes)
        stats.bytes_by_kind[kind] = stats.bytes_by_kind.get(kind, 0.0) + traffic
        stats.count_by_kind[kind] = stats.count_by_kind.get(kind, 0) + 1
    return stats


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float               # per device
    hlo_bytes: float               # per device
    collective_bytes: float        # per device
    t_compute: float
    t_memory: float
    t_collective: float
    model_flops: float             # 6ND useful flops, whole step, global
    collectives: dict[str, float] = dataclasses.field(default_factory=dict)
    collective_counts: dict[str, int] = dataclasses.field(default_factory=dict)
    peak_memory_bytes: Optional[float] = None

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def t_bound(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_flops_fraction(self) -> float:
        """MODEL_FLOPS / (chips x HLO_FLOPs): remat/redundancy waste."""
        total_hlo = self.hlo_flops * self.chips
        return self.model_flops / total_hlo if total_hlo else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Achievable MFU upper bound: useful-flop time / bound time."""
        ideal = self.model_flops / (self.chips * TPU_V5E.peak_flops)
        return ideal / self.t_bound if self.t_bound else 0.0

    def row(self) -> dict[str, Any]:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips,
            "hlo_flops": self.hlo_flops, "hlo_bytes": self.hlo_bytes,
            "collective_bytes": self.collective_bytes,
            "t_compute": self.t_compute, "t_memory": self.t_memory,
            "t_collective": self.t_collective, "dominant": self.dominant,
            "model_flops": self.model_flops,
            "useful_flops_fraction": self.useful_flops_fraction,
            "roofline_fraction": self.roofline_fraction,
            "collectives": self.collectives,
            "collective_counts": self.collective_counts,
            "peak_memory_bytes": self.peak_memory_bytes,
        }


def roofline_from_compiled(
    compiled,
    *,
    arch: str,
    shape: str,
    mesh_name: str,
    chips: int,
    model_flops: float,
    hw: HwConstants = TPU_V5E,
    hlo_text: Optional[str] = None,
) -> RooflineReport:
    """Build the three-term report from a ``jax.stages.Compiled``."""
    cost = compiled.cost_analysis()
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))
    text = hlo_text if hlo_text is not None else compiled.as_text()
    coll = collective_stats_from_hlo(text, chips)
    mem = None
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            mem = float(
                ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes - ma.alias_size_in_bytes
            )
    except Exception:
        pass
    link_bw_total = hw.link_bw * hw.links_per_chip
    return RooflineReport(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        hlo_flops=flops, hlo_bytes=byts,
        collective_bytes=coll.total_bytes,
        t_compute=flops / hw.peak_flops,
        t_memory=byts / hw.hbm_bw,
        t_collective=coll.total_bytes / link_bw_total,
        model_flops=model_flops,
        collectives=dict(coll.bytes_by_kind),
        collective_counts=dict(coll.count_by_kind),
        peak_memory_bytes=mem,
    )


def roofline_from_numbers(
    *,
    arch: str,
    shape: str,
    mesh_name: str,
    chips: int,
    flops: float,
    hbm_bytes: float,
    coll_bytes: dict[str, float],
    model_flops: float,
    peak_memory: Optional[float] = None,
    hw: HwConstants = TPU_V5E,
) -> RooflineReport:
    """Build the report from the analytic cost model (per-device numbers).

    Used by the dry-run because XLA-CPU cost_analysis counts while-loop
    bodies once (verified in tests/test_costmodel.py); the raw compiled
    numbers are recorded alongside for corroboration."""
    total = sum(coll_bytes.values())
    return RooflineReport(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        hlo_flops=flops, hlo_bytes=hbm_bytes, collective_bytes=total,
        t_compute=flops / hw.peak_flops,
        t_memory=hbm_bytes / hw.hbm_bw,
        t_collective=total / (hw.link_bw * hw.links_per_chip),
        model_flops=model_flops,
        collectives=dict(coll_bytes),
        peak_memory_bytes=peak_memory,
    )


def kernel_roofline_seconds(flops: float, byts: float, programs: float,
                            hw: Any) -> float:
    """Per-kernel roofline: ``max(compute, memory) + launch overhead``.

    ``hw`` is a ``core.hw.TpuParams`` (duck-typed to avoid a hard import:
    only ``peak_flops_bf16``, ``hbm_bw``, ``launch_overhead_cycles`` and
    ``clock_hz`` are read).  This is THE model the tuner's per-kernel cost
    functions are built from (``tuner.dispatch``) and the model whose
    parameters ``profiler.calibrate`` fits against measured traces — one
    definition, so a calibrated ``TpuParams`` changes both.
    """
    t = max(flops / hw.peak_flops_bf16, byts / hw.hbm_bw)
    return t + programs * hw.launch_overhead_cycles / hw.clock_hz


def model_flops_per_step(
    n_params_active: float,
    tokens_per_step: float,
    *,
    training: bool = True,
) -> float:
    """MODEL_FLOPS = 6·N·D for training (fwd 2ND + bwd 4ND), 2·N·D inference."""
    mult = 6.0 if training else 2.0
    return mult * n_params_active * tokens_per_step


def fmt_seconds(t: float) -> str:
    if t == 0:
        return "0"
    exp = int(math.floor(math.log10(abs(t))))
    if exp >= 0:
        return f"{t:.3f}s"
    if exp >= -3:
        return f"{t*1e3:.3f}ms"
    if exp >= -6:
        return f"{t*1e6:.2f}us"
    return f"{t*1e9:.1f}ns"
