"""Timed execution of kernel plans — the observation side of the loop.

The paper derives its mapping rule from *measured* execution traces; the
tuner (``repro.tuner``) so far refines candidates only against analytic
roofline cost.  This module supplies the missing primitive: run one
``(kernel, workload, decision value)`` point on the device actually
attached to the process and report robust wall-clock statistics plus the
compiler's own ``cost_analysis()`` numbers.

Design points:

  * **compile once, time many** — the kernel is jitted and compiled
    before the timed region; every repeat calls the compiled executable
    and blocks on the result (``block_until_ready``), so tracing and
    dispatch-queue effects never pollute the samples;
  * **median/IQR, not mean** — one preempted repeat must not move the
    reported cost (shared machines, interpret mode on CI);
  * **normalized forms** — per-program and per-byte seconds, so traces
    taken at different sizes are comparable and ``calibrate`` can fit
    hardware parameters across workloads;
  * **synthetic inputs** — measurement owns its operands (built from the
    workload *description*, never user arrays), so a sweep needs nothing
    but a desc dict and records are reproducible from the store alone.

Records serialize to JSON (``Measurement.to_record``/``from_record``) and
persist in ``profiler.store``.
"""

from __future__ import annotations

import dataclasses
import statistics
import time
from typing import Any, Callable, Optional

from repro.core.hw import TpuParams, ceil_div

__all__ = [
    "TimingStats",
    "Measurement",
    "time_callable",
    "measure_value",
    "canon_value",
    "value_key",
    "record_key",
    "SynthSpec",
    "SYNTH_REGISTRY",
    "supported_kernels",
]


# --------------------------------------------------------------------------- #
# Decision-value canonicalization (shared with store/cost)
# --------------------------------------------------------------------------- #


def canon_value(value: Any):
    """Canonical Python form of a decision value: int or tuple of ints.

    JSON round-trips lists for tuples; cache replay hands back either.
    One canonical form means store keys and equality checks never depend
    on which path a value travelled.
    """
    if isinstance(value, (list, tuple)):
        return tuple(int(v) for v in value)
    return int(value)


def value_key(value: Any) -> str:
    """Stable string rendering of a canonical value (store key suffix)."""
    v = canon_value(value)
    if isinstance(v, tuple):
        return "x".join(str(x) for x in v)
    return str(v)


def record_key(hw_key: str, sig_key: str, value: Any) -> str:
    """THE trace-record identity — the one composition both
    ``Measurement.key`` and ``TraceStore.full_key`` use, so writes and
    lookups can never desynchronize."""
    return f"{hw_key}::{sig_key}::{value_key(value)}"


# --------------------------------------------------------------------------- #
# Timing
# --------------------------------------------------------------------------- #


@dataclasses.dataclass(frozen=True)
class TimingStats:
    """Robust summary of one timed sweep (seconds)."""

    reps: int
    warmup: int
    median_s: float
    iqr_s: float
    mean_s: float
    min_s: float
    max_s: float

    @classmethod
    def from_samples(cls, samples: list[float], warmup: int) -> "TimingStats":
        if not samples:
            raise ValueError("no timing samples")
        n = len(samples)
        med = statistics.median(samples)
        if n >= 4:
            q = statistics.quantiles(samples, n=4)
            iqr = q[2] - q[0]
        else:
            iqr = max(samples) - min(samples)
        return cls(reps=n, warmup=warmup, median_s=med, iqr_s=iqr,
                   mean_s=statistics.fmean(samples),
                   min_s=min(samples), max_s=max(samples))

    def as_dict(self) -> dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "TimingStats":
        return cls(reps=int(d["reps"]), warmup=int(d["warmup"]),
                   median_s=float(d["median_s"]), iqr_s=float(d["iqr_s"]),
                   mean_s=float(d["mean_s"]), min_s=float(d["min_s"]),
                   max_s=float(d["max_s"]))


def time_callable(fn: Callable[[], Any], *, warmup: int = 1,
                  reps: int = 3) -> TimingStats:
    """Time ``fn()`` with warmup discarded and every repeat synchronized.

    ``fn`` should return the computation's output (arrays); each sample
    spans call + ``jax.block_until_ready`` so asynchronous dispatch can
    never report a queue-depth artefact as kernel time.
    """
    import jax

    for _ in range(max(0, warmup)):
        jax.block_until_ready(fn())
    samples = []
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        samples.append(time.perf_counter() - t0)
    return TimingStats.from_samples(samples, warmup=max(0, warmup))


# --------------------------------------------------------------------------- #
# Measurement record
# --------------------------------------------------------------------------- #

#: bump when the record fields change; part of the trace-store header.
MEASUREMENT_VERSION = 1


@dataclasses.dataclass(frozen=True)
class Measurement:
    """One observed (kernel, workload, hardware, decision value) point.

    ``flops``/``hbm_bytes`` are the *analytic* workload features (same
    vocabulary as the tuner cost models — what ``calibrate`` fits
    against); ``xla_flops``/``xla_bytes`` are the compiler's
    ``cost_analysis()`` numbers recorded for corroboration, when the
    backend exposes them.
    """

    kernel: str
    hw_key: str
    sig_key: str
    value: Any                       # canonical decision value
    stats: TimingStats
    desc: Optional[dict] = None      # workload description (re-measurable)
    programs: Optional[int] = None   # grid programs launched
    flops: Optional[float] = None    # analytic, whole workload
    hbm_bytes: Optional[float] = None
    xla_flops: Optional[float] = None
    xla_bytes: Optional[float] = None
    backend: str = ""                # jax.default_backend() at record time
    interpret: bool = False
    source: str = "live"             # live | fixture
    created: float = 0.0

    @property
    def median_s(self) -> float:
        return self.stats.median_s

    @property
    def per_program_s(self) -> Optional[float]:
        if not self.programs:
            return None
        return self.stats.median_s / self.programs

    @property
    def per_byte_s(self) -> Optional[float]:
        if not self.hbm_bytes:
            return None
        return self.stats.median_s / self.hbm_bytes

    @property
    def key(self) -> str:
        """Store key: hardware :: workload :: decision value."""
        return record_key(self.hw_key, self.sig_key, self.value)

    def to_record(self) -> dict[str, Any]:
        v = canon_value(self.value)
        return {
            "kernel": self.kernel,
            "hw_key": self.hw_key,
            "sig_key": self.sig_key,
            "value": list(v) if isinstance(v, tuple) else v,
            "stats": self.stats.as_dict(),
            "desc": dict(self.desc) if self.desc is not None else None,
            "programs": self.programs,
            "flops": self.flops,
            "hbm_bytes": self.hbm_bytes,
            "xla_flops": self.xla_flops,
            "xla_bytes": self.xla_bytes,
            "backend": self.backend,
            "interpret": self.interpret,
            "source": self.source,
            "created": self.created,
        }

    @classmethod
    def from_record(cls, d: dict) -> "Measurement":
        return cls(
            kernel=d["kernel"], hw_key=d["hw_key"], sig_key=d["sig_key"],
            value=canon_value(d["value"]),
            stats=TimingStats.from_dict(d["stats"]),
            desc=d.get("desc"),
            programs=d.get("programs"),
            flops=d.get("flops"), hbm_bytes=d.get("hbm_bytes"),
            xla_flops=d.get("xla_flops"), xla_bytes=d.get("xla_bytes"),
            backend=d.get("backend", ""),
            interpret=bool(d.get("interpret", False)),
            source=d.get("source", "live"),
            created=float(d.get("created", 0.0)),
        )


# --------------------------------------------------------------------------- #
# Synthetic operands + analytic features per kernel
# --------------------------------------------------------------------------- #


@dataclasses.dataclass(frozen=True)
class SynthSpec:
    """How to measure one registered kernel without user arrays.

    ``make``     desc -> (args, kwargs) for KernelSpec.run
    ``programs`` (desc, plan) -> grid programs the plan launches
    ``features`` desc -> (flops, hbm_bytes) analytic workload features
    """

    make: Callable[[dict], tuple[tuple, dict]]
    programs: Callable[[dict, Any], int]
    features: Callable[[dict], tuple[float, float]]


SYNTH_REGISTRY: dict[str, SynthSpec] = {}


def supported_kernels() -> list[str]:
    return sorted(SYNTH_REGISTRY)


def _rand(shape, dtype: str, seed: int = 0, scale: float = 1.0):
    """Deterministic operand arrays (numpy RNG -> device array)."""
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(seed)
    x = rng.standard_normal(shape, dtype=np.float32) * scale
    return jnp.asarray(x).astype(dtype)


def _grid_programs(plan) -> int:
    g = getattr(plan, "grid", None)
    if g is None:
        return 1
    if isinstance(g, (tuple, list)):
        n = 1
        for d in g:
            n *= int(d)
        return n
    return int(g)


def _populate_synth() -> None:
    import jax.numpy as jnp

    def vector(desc):
        return ((_rand((desc["n"],), desc["dtype"], 0),
                 _rand((desc["n"],), desc["dtype"], 1)), {})

    def saxpy_make(desc):
        (x, y), _ = vector(desc)
        return ((jnp.asarray(1.5, x.dtype), x, y), {})

    def vec_feat(flops_per_elem):
        def f(desc):
            n, db = desc["n"], desc["dtype_bytes"]
            return flops_per_elem * n, 3.0 * n * db
        return f

    SYNTH_REGISTRY["vecadd"] = SynthSpec(
        make=vector, programs=lambda d, p: _grid_programs(p),
        features=vec_feat(1.0))
    SYNTH_REGISTRY["saxpy"] = SynthSpec(
        make=saxpy_make, programs=lambda d, p: _grid_programs(p),
        features=vec_feat(2.0))

    SYNTH_REGISTRY["matmul"] = SynthSpec(
        make=lambda d: ((_rand((d["m"], d["k"]), d["dtype"], 0, 0.1),
                         _rand((d["k"], d["n"]), d["dtype"], 1, 0.1)), {}),
        programs=lambda d, p: _grid_programs(p),
        features=lambda d: (
            2.0 * d["m"] * d["n"] * d["k"],
            (d["m"] * d["k"] + d["k"] * d["n"] + 2.0 * d["m"] * d["n"])
            * d["dtype_bytes"]))

    def flash_make(d):
        q = _rand((d["seq_q"], d["head_dim"]), d["dtype"], 0, 0.2)
        k = _rand((d["seq_kv"], d["head_dim"]), d["dtype"], 1, 0.2)
        v = _rand((d["seq_kv"], d["head_dim"]), d["dtype"], 2)
        return (q, k, v), {"causal": d["causal"]}

    def flash_feat(d):
        hd = max(d["head_dim"], 128)
        flops = 4.0 * d["seq_q"] * d["seq_kv"] * hd
        if d["causal"]:
            flops *= 0.5
        return flops, 2.0 * (d["seq_q"] + d["seq_kv"]) * hd * d["dtype_bytes"]

    SYNTH_REGISTRY["flash_attention"] = SynthSpec(
        make=flash_make,
        programs=lambda d, p: p.grid_q * ceil_div(d["seq_kv"], p.block_k),
        features=flash_feat)

    SYNTH_REGISTRY["rmsnorm"] = SynthSpec(
        make=lambda d: ((_rand((d["tokens"], d["d"]), d["dtype"], 0),
                         _rand((d["d"],), d["dtype"], 1)), {}),
        programs=lambda d, p: ceil_div(d["tokens"], int(p)),
        features=lambda d: (4.0 * d["tokens"] * d["d"],
                            2.0 * d["tokens"] * d["d"] * d["dtype_bytes"]))

    SYNTH_REGISTRY["decode_attention"] = SynthSpec(
        make=lambda d: ((_rand((d["d"],), d["dtype"], 0, 0.2),
                         _rand((d["s"], d["d"]), d["dtype"], 1, 0.2),
                         _rand((d["s"], d["d"]), d["dtype"], 2),
                         d["s"]), {}),
        programs=lambda d, p: ceil_div(d["s"], int(p)),
        features=lambda d: (4.0 * d["s"] * d["d"],
                            2.0 * d["s"] * d["d"] * d["dtype_bytes"]))

    def paged_make(d):
        import numpy as np
        s, hd, pb = d["s"], d["d"], d["page_block"]
        nb = d["max_blocks_per_row"]
        q = _rand((1, 1, 1, hd), d["dtype"], 0, 0.2)
        k = _rand((1, s, 1, hd), d["dtype"], 1, 0.2)
        v = _rand((1, s, 1, hd), d["dtype"], 2)
        # a nontrivial page permutation: the indirection must actually
        # scatter, or fused-vs-gather comparisons measure nothing
        need = -(-s // pb)
        rng = np.random.default_rng(3)
        tb = np.full((1, nb), -1, np.int32)
        tb[0, :need] = rng.permutation(need).astype(np.int32)
        return ((q, k, v, jnp.asarray(tb), s), {"page_block": pb})

    SYNTH_REGISTRY["paged_decode"] = SynthSpec(
        make=paged_make,
        # grid = (steps, pages-per-step) per row — the fused schedule
        programs=lambda d, p: (ceil_div(d["s"], int(p))
                               * max(1, int(p) // d["page_block"])),
        features=lambda d: (4.0 * d["s"] * d["d"],
                            2.0 * d["s"] * d["d"] * d["dtype_bytes"]))

    SYNTH_REGISTRY["gaussian_blur"] = SynthSpec(
        make=lambda d: ((_rand((d["h"], d["w"]), d["dtype"], 0),),
                        {"ksize": d["ksize"]}),
        programs=lambda d, p: 2 * ceil_div(d["h"], int(p)),  # two passes
        features=lambda d: (4.0 * d["ksize"] * d["h"] * d["w"],
                            4.0 * d["h"] * d["w"] * d["dtype_bytes"]))

    def gcn_make(d):
        import numpy as np
        rng = np.random.default_rng(0)
        adj = (rng.random((d["n"], d["n"])) < 0.05).astype(np.float32)
        adj = adj / np.maximum(adj.sum(1, keepdims=True), 1.0)
        return ((jnp.asarray(adj).astype(d["dtype"]),
                 _rand((d["n"], d["f"]), d["dtype"], 1)),
                {"block_s": d["block_s"]})

    SYNTH_REGISTRY["gcn_agg"] = SynthSpec(
        make=gcn_make,
        programs=lambda d, p: (ceil_div(d["n"], int(p))
                               * ceil_div(d["n"], d["block_s"])),
        features=lambda d: (2.0 * d["n"] * d["n"] * d["f"],
                            (d["n"] + 2.0 * d["f"]) * d["n"]
                            * d["dtype_bytes"]))

    SYNTH_REGISTRY["nn_search"] = SynthSpec(
        make=lambda d: ((_rand((d["nq"], d["d"]), d["dtype"], 0),
                         _rand((d["nr"], d["d"]), d["dtype"], 1)),
                        {"block_r": d["block_r"]}),
        programs=lambda d, p: (ceil_div(d["nq"], int(p))
                               * ceil_div(d["nr"], d["block_r"])),
        features=lambda d: (3.0 * d["nq"] * d["nr"] * d["d"],
                            2.0 * d["nq"] * d["d"] * d["dtype_bytes"]))


_populate_synth()


# --------------------------------------------------------------------------- #
# The harness
# --------------------------------------------------------------------------- #


def measure_value(
    kernel: str,
    desc: dict,
    value: Any,
    hw: TpuParams,
    *,
    interpret: Optional[bool] = None,
    warmup: int = 1,
    reps: int = 3,
    with_cost_analysis: bool = True,
) -> Measurement:
    """Measure one decision value of one workload on the live backend.

    Builds the full legalized plan via the kernel's registered
    ``plan_from_value``, synthesizes operands from ``desc``, compiles the
    run function once, and times the compiled executable.
    ``interpret=None`` auto-selects: compiled Pallas on TPU, interpret
    mode elsewhere (Pallas cannot compile on CPU).  Raises
    ``ValueError`` for kernels with no run function or no synthesizer
    (callers that must never fail — dispatch — check
    ``kernel in SYNTH_REGISTRY`` first).
    """
    import jax

    from repro.tuner.dispatch import KERNEL_REGISTRY
    from repro.tuner.signature import hardware_key

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    spec = KERNEL_REGISTRY[kernel]
    if spec.run is None:
        raise ValueError(f"kernel {kernel!r} is plan-only: nothing to run")
    synth = SYNTH_REGISTRY.get(kernel)
    if synth is None:
        raise ValueError(f"kernel {kernel!r} has no input synthesizer")

    value = canon_value(value)
    plan = spec.plan_from_value(desc, hw, value)
    args, kwargs = synth.make(desc)

    def fn(*arrays):
        return spec.run(plan, hw, interpret, *arrays, **kwargs)

    # a compile failure raises: a candidate the compiler refuses must
    # not be timed through some other executable
    compiled = jax.jit(fn).lower(*args).compile()
    runner = lambda: compiled(*args)
    xla_flops = xla_bytes = None
    if with_cost_analysis:
        try:
            cost = compiled.cost_analysis()
            if cost:
                xla_flops = float(cost.get("flops", 0.0)) or None
                xla_bytes = float(cost.get("bytes accessed", 0.0)) or None
        except Exception:
            pass          # stats are optional; the executable is not

    stats = time_callable(runner, warmup=warmup, reps=reps)
    flops, byts = synth.features(desc)
    sig = spec.sig(desc, "tuned")
    return Measurement(
        kernel=kernel, hw_key=hardware_key(hw), sig_key=sig.key,
        value=value, stats=stats, desc=dict(desc),
        programs=int(synth.programs(desc, plan)),
        flops=float(flops), hbm_bytes=float(byts),
        xla_flops=xla_flops, xla_bytes=xla_bytes,
        backend=jax.default_backend(), interpret=interpret,
        source="live", created=time.time(),
    )
