"""Unified kernel dispatch: Eq. 1 seed -> cache -> refine -> memoize.

Every Pallas kernel in the repo routes its mapping decision through this
module (``kernels.ops`` for the jit'd public API, ``tuned_call`` for
direct invocation).  The flow for ``MappingPolicy.TUNED``:

  1. build the canonical workload signature + hardware key
     (``tuner.signature``);
  2. consult the ``TuningCache`` — a warm hit rebuilds the full plan from
     the cached decision variables with ZERO refine probes (the
     acceptance criterion benchmarked in ``benchmarks/tuner_bench.py``);
  3. on a miss, seed with the Eq. 1 plan (``core.mapper``) and refine it
     with ``core.autotune.refine_discrete`` against the kernel's roofline
     cost model (compute/memory max + per-program launch overhead);
  4. memoize the winner — only the decision variables are persisted, the
     derived plan fields are recomputed on decode so cached entries
     survive planner evolution.

Kernels without a cost model (and the mesh tier, whose objective is HBM
fit rather than a differentiable cost) fall back cleanly to the Eq. 1
seed: still cached, zero probes, never an error.

``NAIVE`` / ``FIXED`` / ``AUTO`` bypass the cache entirely and hit the
pure planners — dispatch adds nothing but a function call for them.

``measure="cached"|"live"`` upgrades step 3: the roofline ranks the
candidate neighbourhood, and the top-K survivors are re-judged by
recorded (or live) measurements from the ``repro.profiler`` trace store
— the paper's evidence loop, closed (see docs/TUNING.md).  Step 2 is
untouched: warm hits never measure.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Optional, Sequence

from repro.core.autotune import refine_discrete
from repro.core.hw import TpuParams, ceil_div, detect
from repro.core.mapper import (MappingPolicy, MeshPlan,
                               attention_plan_for_blocks,
                               matmul_plan_for_blocks, plan_attention_blocks,
                               plan_matmul_blocks, plan_microbatch,
                               plan_vector_blocks, vector_plan_for_block)
from repro.core.roofline import kernel_roofline_seconds
from repro.core.workload import saxpy as saxpy_workload
from repro.core.workload import vecadd as vecadd_workload
from repro.tuner.cache import TuningCache, default_cache_path
from repro.tuner.signature import (WorkloadSignature, hardware_key,
                                   workload_signature)

__all__ = [
    "KernelSpec",
    "KERNEL_REGISTRY",
    "MEASURE_MODES",
    "ResolveInfo",
    "resolve_plan",
    "tuned_call",
    "get_default_cache",
    "set_default_cache",
]

_INF = float("inf")


# --------------------------------------------------------------------------- #
# Default cache
# --------------------------------------------------------------------------- #

_default_cache: Optional[TuningCache] = None


def get_default_cache() -> TuningCache:
    """Process-wide cache, created lazily at the default path.

    Example::

        print(get_default_cache().stats.as_dict())
    """
    global _default_cache
    if _default_cache is None:
        _default_cache = TuningCache(default_cache_path())
    return _default_cache


def set_default_cache(cache: Optional[TuningCache]) -> None:
    """Swap the process-wide cache (None resets to lazy default).

    Example::

        set_default_cache(TuningCache(path=None))   # hermetic tests
    """
    global _default_cache
    _default_cache = cache


# --------------------------------------------------------------------------- #
# Kernel registry
# --------------------------------------------------------------------------- #


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """How one kernel plugs into the dispatcher (see docs/TUNING.md).

    ``describe``        (*args, **kw) -> desc dict of static parameters
    ``sig``             (desc, policy) -> WorkloadSignature
    ``seed_plan``       (desc, hw, policy) -> plan via core.mapper
    ``plan_value``      plan -> JSON-able decision variables
    ``plan_from_value`` (desc, hw, value) -> full plan (legalizes!)
    ``cost_model``      (desc, hw) -> cost(value)->seconds, or None
                        (None == clean fallback to the Eq. 1 seed)
    ``candidates``      (desc, hw, seed_value) -> values to probe
    ``run``             (plan, hw, interpret, *args, **kw) -> result

    Example::

        register_kernel(KernelSpec(name="mykernel", describe=...,
                                   sig=..., seed_plan=..., ...))
    """

    name: str
    describe: Callable[..., dict]
    sig: Callable[[dict, Any], WorkloadSignature]
    seed_plan: Callable[[dict, TpuParams, MappingPolicy], Any]
    plan_value: Callable[[Any], Any]
    plan_from_value: Callable[[dict, TpuParams, Any], Any]
    cost_model: Optional[Callable[[dict, TpuParams], Callable[[Any], float]]]
    candidates: Callable[[dict, TpuParams, Any], Sequence[Any]]
    run: Optional[Callable[..., Any]] = None


KERNEL_REGISTRY: dict[str, KernelSpec] = {}


def register_kernel(spec: KernelSpec) -> KernelSpec:
    """Install a ``KernelSpec`` into the dispatch registry (returns it,
    so modules can register at import time).

    Example::

        SPEC = register_kernel(KernelSpec(name="mykernel", ...))
    """
    KERNEL_REGISTRY[spec.name] = spec
    return spec


# --------------------------------------------------------------------------- #
# Resolution
# --------------------------------------------------------------------------- #


@dataclasses.dataclass(frozen=True)
class ResolveInfo:
    """Provenance of one resolved plan (tests + tuner_bench assert on it).

    Example::

        plan, info = resolve_plan("decode_attention", hw, "tuned", desc)
        assert info.source in ("cache", "refined", "measured")
    """

    source: str                 # planner | cache | refined | measured | fallback
    probes: int                 # refine probes spent THIS resolution
    refine_time_s: float = 0.0
    cost: Optional[float] = None
    seed_cost: Optional[float] = None
    sig_key: Optional[str] = None
    measured: int = 0           # live measurements spent THIS resolution


# Warm-path memos.  ``_KEY_MEMO`` caches (signature, hw key, full cache
# key) per (kernel, desc, hw); ``_PLAN_MEMO`` caches the decoded plan +
# ResolveInfo per cache entry.  Both only shortcut recomputation of pure
# functions of their keys — the TuningCache stays the source of truth
# (its stats still see every warm dispatch as a hit) and a changed cache
# value invalidates the plan memo by comparison.
_MEMO_CAP = 65536
_KEY_MEMO: dict[tuple, tuple[WorkloadSignature, str, str]] = {}
_PLAN_MEMO: dict[str, tuple[Any, Any, ResolveInfo]] = {}


def _memo_keys(spec: KernelSpec, desc: dict, policy: MappingPolicy,
               hw: TpuParams) -> tuple[WorkloadSignature, str, str]:
    try:
        mk = (spec.name, tuple(sorted(desc.items())), hw)
    except TypeError:                 # unhashable desc value: skip the memo
        mk = None
    else:
        hit = _KEY_MEMO.get(mk)
        if hit is not None:
            return hit
    sig = spec.sig(desc, policy)
    hwk = hardware_key(hw)
    keys = (sig, hwk, TuningCache.full_key(hwk, sig))
    if mk is not None:
        if len(_KEY_MEMO) > _MEMO_CAP:
            _KEY_MEMO.clear()
        _KEY_MEMO[mk] = keys
    return keys


#: valid ``measure=`` modes (see docs/TUNING.md):
#:   off    — analytic roofline refinement only (the PR-1 behaviour);
#:   cached — misses re-rank the roofline top-K by *recorded* traces
#:            (zero device work: fixture/CI safe);
#:   live   — misses measure unrecorded top-K survivors on the device
#:            and persist the traces.
#: Warm cache hits never measure in ANY mode — the hit path above the
#: miss branch does not touch the profiler at all.
MEASURE_MODES = ("off", "cached", "live")


def resolve_plan(
    kernel: str,
    hw: TpuParams,
    policy: MappingPolicy | str,
    desc: dict,
    cache: Optional[TuningCache] = None,
    *,
    measure: str = "off",
    store: Optional[Any] = None,
    measure_opts: Optional[dict] = None,
) -> tuple[Any, ResolveInfo]:
    """Resolve the mapping plan for one workload under one policy.

    Example::

        desc = {"s": 1024, "d": 64, "dtype": "float32", "dtype_bytes": 4}
        block, info = resolve_plan("decode_attention", hw,
                                   MappingPolicy.TUNED, desc)
    """
    # observability: when a tracer is ambient (obs.trace — the serve
    # router installs its own around cold resolutions), every resolve
    # becomes a span carrying provenance + probe spend.  Lazy import:
    # obs sits above tuner in the layering, and the null-tracer fast
    # path costs one attribute check.
    from repro.obs.trace import get_tracer

    tracer = get_tracer()
    if not tracer.enabled:
        return _resolve_plan_impl(kernel, hw, policy, desc, cache,
                                  measure=measure, store=store,
                                  measure_opts=measure_opts)
    with tracer.span("resolve_plan", kernel=kernel,
                     measure=measure) as sp:
        plan, info = _resolve_plan_impl(kernel, hw, policy, desc, cache,
                                        measure=measure, store=store,
                                        measure_opts=measure_opts)
        sp.set(source=info.source, probes=info.probes,
               measured=info.measured)
        return plan, info


def _resolve_plan_impl(
    kernel: str,
    hw: TpuParams,
    policy: MappingPolicy | str,
    desc: dict,
    cache: Optional[TuningCache] = None,
    *,
    measure: str = "off",
    store: Optional[Any] = None,
    measure_opts: Optional[dict] = None,
) -> tuple[Any, ResolveInfo]:
    """The untraced resolution flow (seed -> cache -> refine -> memoize);
    ``resolve_plan`` is the public spanned wrapper."""
    spec = KERNEL_REGISTRY[kernel]
    if measure not in MEASURE_MODES:
        raise ValueError(f"measure must be one of {MEASURE_MODES}, "
                         f"got {measure!r}")
    if not isinstance(policy, MappingPolicy):
        policy = MappingPolicy(policy)
    if policy is not MappingPolicy.TUNED:
        return spec.seed_plan(desc, hw, policy), ResolveInfo("planner", 0)

    cache = cache if cache is not None else get_default_cache()
    sig, hwk, fk = _memo_keys(spec, desc, policy, hw)
    entry = cache.get_by_key(fk)
    if entry is not None:
        value = entry["plan"]["value"]
        memo = _PLAN_MEMO.get(fk)
        if memo is not None and memo[0] == value:
            return memo[1], memo[2]
        plan = spec.plan_from_value(desc, hw, value)
        info = ResolveInfo("cache", 0, cost=entry.get("cost"),
                           seed_cost=entry.get("seed_cost"), sig_key=sig.key)
        if len(_PLAN_MEMO) > _MEMO_CAP:
            _PLAN_MEMO.clear()
        _PLAN_MEMO[fk] = (value, plan, info)
        return plan, info

    seed = spec.seed_plan(desc, hw, policy)
    if spec.cost_model is None:
        cache.put(hwk, sig, {"value": spec.plan_value(seed)}, probes=0)
        return seed, ResolveInfo("fallback", 0, sig_key=sig.key)

    if measure != "off":
        return _resolve_measured(spec, desc, hw, cache, sig, hwk,
                                 measure, store, measure_opts)

    t0 = time.perf_counter()
    cost_fn = spec.cost_model(desc, hw)
    seed_value = spec.plan_value(seed)
    cands = spec.candidates(desc, hw, seed_value)
    res = refine_discrete(seed_value, cost_fn, candidates=cands)
    dt = time.perf_counter() - t0
    plan = spec.plan_from_value(desc, hw, res.best)
    cache.put(hwk, sig, {"value": spec.plan_value(plan)},
              cost=res.best_cost, seed_cost=res.seed_cost,
              probes=res.probes, refine_time_s=dt)
    return plan, ResolveInfo("refined", res.probes, refine_time_s=dt,
                             cost=res.best_cost, seed_cost=res.seed_cost,
                             sig_key=sig.key)


def _resolve_measured(spec, desc, hw, cache, sig, hwk, measure, store,
                      measure_opts):
    """TUNED cache miss under ``measure="cached"|"live"``: roofline
    prunes, recorded/live measurement picks (profiler.cost.hybrid_refine).
    Falls back to the pure-roofline winner when the store holds no
    evidence for the workload — measured mode never fails a dispatch."""
    # lazy import: profiler builds on tuner, not the other way round
    from repro.profiler.cost import hybrid_refine
    from repro.profiler.store import get_default_store

    store = store if store is not None else get_default_store()
    t0 = time.perf_counter()
    res = hybrid_refine(spec.name, desc, hw, store=store, mode=measure,
                        measure_opts=measure_opts)
    dt = time.perf_counter() - t0
    plan = spec.plan_from_value(desc, hw, res.value)
    measured_seed = None
    if res.source == "measured":
        # seed_cost: measured seconds of the roofline-only winner when
        # recorded — cost/seed_cost then quantify the evidence loop's win
        m = store.get(hwk, sig.key, res.roofline.best)
        measured_seed = m.median_s if m is not None else None
        cost = res.measured_cost
    else:
        cost, measured_seed = res.roofline_cost, res.roofline.seed_cost
    cache.put(hwk, sig, {"value": spec.plan_value(plan)},
              cost=cost, seed_cost=measured_seed, probes=res.probes,
              refine_time_s=dt,
              extra={"measured": res.source == "measured",
                     "measure_mode": measure})
    # "roofline" fallback reads as a plain model refinement to callers
    source = "measured" if res.source == "measured" else "refined"
    return plan, ResolveInfo(source, res.probes, refine_time_s=dt,
                             cost=cost, seed_cost=measured_seed,
                             sig_key=sig.key,
                             measured=res.live_measurements)


def tuned_call(
    kernel: str,
    *args: Any,
    hw: Optional[TpuParams] = None,
    policy: MappingPolicy | str = MappingPolicy.TUNED,
    cache: Optional[TuningCache] = None,
    interpret: bool = False,
    measure: str = "off",
    store: Optional[Any] = None,
    measure_opts: Optional[dict] = None,
    **kwargs: Any,
) -> Any:
    """Run ``kernel`` with its mapping resolved through the tuner.

    Example::

        out = tuned_call("vecadd", x, y, hw=hw, policy="tuned")

    The single entry point the retrofitted call sites use: signature ->
    cache -> (refine) -> run.  ``hw`` defaults to runtime detection, the
    cache to the process-wide default.  ``measure`` upgrades cache-miss
    refinement from analytic to observed cost ("cached" replays the
    trace store, "live" measures and records) — warm hits are identical
    zero-measurement dict lookups in every mode.
    """
    spec = KERNEL_REGISTRY[kernel]
    if spec.run is None:
        raise ValueError(f"kernel {kernel!r} is plan-only (no run function)")
    hw = hw if hw is not None else detect()
    desc = spec.describe(*args, **kwargs)
    if measure != "off":
        # measurements must characterize the executor THIS call uses —
        # an explicit measure_opts["interpret"] still wins
        measure_opts = {"interpret": interpret, **(measure_opts or {})}
    plan, _ = resolve_plan(kernel, hw, policy, desc, cache,
                           measure=measure, store=store,
                           measure_opts=measure_opts)
    return spec.run(plan, hw, interpret, *args, **kwargs)


# --------------------------------------------------------------------------- #
# Shared helpers for the built-in specs
# --------------------------------------------------------------------------- #


def _legal_int(v: float, lo: int, quantum: int,
               hi: Optional[int] = None) -> int:
    v = max(lo, int(v) // quantum * quantum)
    return min(v, hi) if hi is not None else v


def _scaled_candidates(seed: int, lo: int, quantum: int,
                       hi: Optional[int] = None) -> list[int]:
    """Neighbourhood of the Eq. 1 seed (paper §3): geometric doublings /
    halvings out to 8x plus ±1/±2 quantum steps, so the search sees both
    coarse regime changes and fine padding effects."""
    cands = {_legal_int(seed * f, lo, quantum, hi)
             for f in (0.125, 0.25, 0.5, 1.0, 2.0, 4.0, 8.0)}
    cands |= {_legal_int(seed + d * quantum, lo, quantum, hi)
              for d in (-2, -1, 1, 2)}
    return sorted(cands)


# Both delegate to the ONE model definition in core.roofline so a
# TpuParams calibrated by profiler.calibrate changes every cost model here.
def _launch_s(programs: int, hw: TpuParams) -> float:
    return kernel_roofline_seconds(0.0, 0.0, programs, hw)


def _roofline_s(flops: float, byts: float, hw: TpuParams) -> float:
    return kernel_roofline_seconds(flops, byts, 0, hw)


def _db(x) -> int:
    import numpy as np
    return np.dtype(x).itemsize


def _dt(x) -> str:
    import numpy as np
    return np.dtype(x.dtype).name


# --------------------------------------------------------------------------- #
# 1D elementwise kernels (vecadd, saxpy)
# --------------------------------------------------------------------------- #


def _register_vector(name: str, workload_fn, run_fn, n_arrays: int):
    def describe(*args, **kwargs):
        x = args[-2]  # last two args are the equal-shape vectors
        return {"n": int(x.shape[0]), "dtype": _dt(x),
                "dtype_bytes": x.dtype.itemsize}

    def sig(desc, policy):
        return workload_signature(name, shapes=[(desc["n"],)],
                                  dtypes=[desc["dtype"]], policy=policy)

    def wl(desc):
        return workload_fn(desc["n"], dtype_bytes=desc["dtype_bytes"])

    def seed_plan(desc, hw, policy):
        return plan_vector_blocks(wl(desc), hw, policy, n_streams=n_arrays)

    def plan_from_value(desc, hw, value):
        return vector_plan_for_block(wl(desc), hw, int(value),
                                     MappingPolicy.TUNED,
                                     n_streams=n_arrays)

    def cost_model(desc, hw):
        w = wl(desc)

        def cost(block):
            plan = plan_from_value(desc, hw, block)
            if plan.vmem_bytes > hw.vmem_budget_bytes:
                return _INF
            t = _roofline_s(plan.padded_gws * w.flops_per_iter,
                            plan.padded_gws * w.bytes_per_iter, hw)
            return t + _launch_s(plan.grid, hw)

        return cost

    def candidates(desc, hw, seed_value):
        q = hw.vpu_sublanes * hw.vpu_lanes
        return _scaled_candidates(seed_value, q, q)

    def run(plan, hw, interpret, *args, **kwargs):
        return run_fn(*args, hw=hw, plan=plan, interpret=interpret, **kwargs)

    return register_kernel(KernelSpec(
        name=name, describe=describe, sig=sig, seed_plan=seed_plan,
        plan_value=lambda p: int(p.block_elems),
        plan_from_value=plan_from_value, cost_model=cost_model,
        candidates=candidates, run=run))


# --------------------------------------------------------------------------- #
# Matmul
# --------------------------------------------------------------------------- #


def _register_matmul():
    from repro.kernels.matmul import matmul_pallas

    def describe(a, b, **kwargs):
        return {"m": int(a.shape[0]), "k": int(a.shape[1]),
                "n": int(b.shape[1]), "dtype": _dt(a),
                "dtype_bytes": a.dtype.itemsize}

    def sig(desc, policy):
        return workload_signature(
            "matmul", shapes=[(desc["m"], desc["k"]), (desc["k"], desc["n"])],
            dtypes=[desc["dtype"]], policy=policy)

    def seed_plan(desc, hw, policy):
        return plan_matmul_blocks(desc["m"], desc["n"], desc["k"], hw, policy,
                                  dtype_bytes=desc["dtype_bytes"])

    def plan_from_value(desc, hw, value):
        bm, bn, bk = (int(v) for v in value)
        return matmul_plan_for_blocks(desc["m"], desc["n"], desc["k"], hw,
                                      bm, bn, bk, MappingPolicy.TUNED,
                                      dtype_bytes=desc["dtype_bytes"])

    def cost_model(desc, hw):
        m, n, k = desc["m"], desc["n"], desc["k"]
        db = desc["dtype_bytes"]

        def cost(value):
            plan = plan_from_value(desc, hw, value)
            if plan.vmem_bytes > hw.vmem_budget_bytes:
                return _INF
            gm, gn, gk = plan.grid
            mp, np_, kp = gm * plan.bm, gn * plan.bn, gk * plan.bk
            # A streamed once per n-block, B once per m-block, C written once
            byts = (mp * kp * gn + kp * np_ * gm + 2 * mp * np_) * db
            flops = 2.0 * mp * np_ * kp
            return _roofline_s(flops, byts, hw) + _launch_s(gm * gn * gk, hw)

        return cost

    def candidates(desc, hw, seed_value):
        t = hw.mxu_dim
        seed = tuple(int(v) for v in seed_value)
        cands = {seed}
        for i in range(3):
            lo = 8 if i == 0 else t
            for f in (0.25, 0.5, 2.0, 4.0):
                c = list(seed)
                c[i] = max(lo, int(c[i] * f))
                cands.add(tuple(c))
        # paired bm/bn moves keep the output tile square-ish while the
        # single-dim moves above explore skew
        for f in (0.5, 2.0):
            cands.add((max(8, int(seed[0] * f)), max(t, int(seed[1] * f)),
                       seed[2]))
        return sorted(cands)

    def run(plan, hw, interpret, a, b, **kwargs):
        return matmul_pallas(a, b, hw=hw, plan=plan, interpret=interpret,
                             **kwargs)

    return register_kernel(KernelSpec(
        name="matmul", describe=describe, sig=sig, seed_plan=seed_plan,
        # tuple, not list: refine_discrete's seed-skip compares candidates
        # (tuples) against this value
        plan_value=lambda p: (int(p.bm), int(p.bn), int(p.bk)),
        plan_from_value=plan_from_value, cost_model=cost_model,
        candidates=candidates, run=run))


# --------------------------------------------------------------------------- #
# Flash attention (prefill)
# --------------------------------------------------------------------------- #


def _register_flash_attention():
    from repro.kernels.flash_attention import flash_attention_pallas

    def describe(q, k, v, *, causal=True, **kwargs):
        return {"seq_q": int(q.shape[-2]), "seq_kv": int(k.shape[-2]),
                "head_dim": int(q.shape[-1]), "dtype": _dt(q),
                "dtype_bytes": q.dtype.itemsize, "causal": bool(causal)}

    def sig(desc, policy):
        return workload_signature(
            "flash_attention",
            shapes=[(desc["seq_q"], desc["head_dim"]),
                    (desc["seq_kv"], desc["head_dim"])],
            dtypes=[desc["dtype"]], policy=policy, causal=desc["causal"])

    def seed_plan(desc, hw, policy):
        return plan_attention_blocks(desc["seq_q"], desc["seq_kv"],
                                     desc["head_dim"], hw, policy,
                                     dtype_bytes=desc["dtype_bytes"])

    def plan_from_value(desc, hw, value):
        bq, bk = (int(v) for v in value)
        return attention_plan_for_blocks(desc["seq_q"], desc["seq_kv"],
                                         desc["head_dim"], hw, bq, bk,
                                         MappingPolicy.TUNED,
                                         dtype_bytes=desc["dtype_bytes"])

    def cost_model(desc, hw):
        sq, skv = desc["seq_q"], desc["seq_kv"]
        hd, db = max(desc["head_dim"], 128), desc["dtype_bytes"]

        def cost(value):
            plan = plan_from_value(desc, hw, value)
            if plan.vmem_bytes > hw.vmem_budget_bytes:
                return _INF
            gq = plan.grid_q
            gk = ceil_div(skv, plan.block_k)
            # q/o streamed once, k/v streamed once per q-block
            byts = (2 * sq * hd + 2 * skv * hd * gq) * db
            flops = 4.0 * sq * skv * hd
            if desc["causal"]:
                flops *= 0.5
            return _roofline_s(flops, byts, hw) + _launch_s(gq * gk, hw)

        return cost

    def candidates(desc, hw, seed_value):
        bq0, bk0 = (int(v) for v in seed_value)
        cands = {(bq0, bk0)}
        for f in (0.25, 0.5, 2.0, 4.0):
            cands.add((max(8, int(bq0 * f)), bk0))
            cands.add((bq0, max(128, int(bk0 * f))))
        for f in (0.5, 2.0):
            cands.add((max(8, int(bq0 * f)), max(128, int(bk0 * f))))
        return sorted(cands)

    def run(plan, hw, interpret, q, k, v, **kwargs):
        return flash_attention_pallas(q, k, v, hw=hw, plan=plan,
                                      interpret=interpret, **kwargs)

    return register_kernel(KernelSpec(
        name="flash_attention", describe=describe, sig=sig,
        seed_plan=seed_plan,
        plan_value=lambda p: (int(p.block_q), int(p.block_k)),
        plan_from_value=plan_from_value, cost_model=cost_model,
        candidates=candidates, run=run))


# --------------------------------------------------------------------------- #
# Single-int block kernels (rmsnorm, decode attention, stencil, gcn, nn)
# --------------------------------------------------------------------------- #


def _register_int_block(
    name: str,
    describe: Callable[..., dict],
    sig_shapes: Callable[[dict], list],
    seed_fn: Callable[[dict, TpuParams, MappingPolicy], int],
    run_with_block: Optional[Callable[..., Any]],
    *,
    quantum: int,
    lo: int,
    unit_count: Callable[[dict], int],
    bytes_per_unit: Callable[[dict], float],
    flops_per_unit: Callable[[dict], float],
    vmem_per_block: Callable[[dict, int], int],
    extra_grid: Callable[[dict], int] = lambda d: 1,
    cap: Callable[[dict], Optional[int]] = lambda d: None,
    extras: Sequence[str] = (),
):
    """Register a kernel whose whole mapping decision is ONE block size.

    The cost model is the shared grid roofline: padded units x per-unit
    bytes/flops, plus per-program launch overhead, with a VMEM-overflow
    rejection — exactly the structure every row/block-planned kernel in
    ``kernels/`` shares.
    """

    def sig(desc, policy):
        ex = {k: desc[k] for k in extras}
        return workload_signature(name, shapes=sig_shapes(desc),
                                  dtypes=[desc["dtype"]], policy=policy, **ex)

    def plan_from_value(desc, hw, value):
        hi = cap(desc)
        block = _legal_int(int(value), lo, quantum,
                           hi if hi is not None else None)
        return block

    def seed_plan(desc, hw, policy):
        return plan_from_value(desc, hw, seed_fn(desc, hw, policy))

    def cost_model(desc, hw):
        units = unit_count(desc)
        bpu, fpu = bytes_per_unit(desc), flops_per_unit(desc)
        eg = extra_grid(desc)

        def cost(block):
            block = plan_from_value(desc, hw, block)
            if vmem_per_block(desc, block) > hw.vmem_budget_bytes:
                return _INF
            g = ceil_div(units, block)
            padded = g * block
            return (_roofline_s(padded * fpu, padded * bpu, hw)
                    + _launch_s(g * eg, hw))

        return cost

    def candidates(desc, hw, seed_value):
        return _scaled_candidates(int(seed_value), lo, quantum, cap(desc))

    run = None
    if run_with_block is not None:
        def run(plan, hw, interpret, *args, **kwargs):
            return run_with_block(plan, hw, interpret, *args, **kwargs)

    return register_kernel(KernelSpec(
        name=name, describe=describe, sig=sig, seed_plan=seed_plan,
        plan_value=int, plan_from_value=plan_from_value,
        cost_model=cost_model, candidates=candidates, run=run))


def _register_rmsnorm():
    from repro.kernels.rmsnorm import plan_rows, rmsnorm_pallas

    def describe(x, gamma, **kwargs):
        return {"tokens": int(x.shape[0]), "d": int(x.shape[1]),
                "dtype": _dt(x), "dtype_bytes": x.dtype.itemsize}

    return _register_int_block(
        "rmsnorm", describe,
        sig_shapes=lambda d: [(d["tokens"], d["d"])],
        seed_fn=lambda d, hw, pol: plan_rows(d["tokens"], d["d"], hw, pol,
                                             d["dtype_bytes"]),
        run_with_block=lambda block, hw, interp, x, gamma, **kw:
            rmsnorm_pallas(x, gamma, hw=hw, block_rows=block,
                           interpret=interp, **kw),
        quantum=8, lo=8,
        unit_count=lambda d: d["tokens"],
        bytes_per_unit=lambda d: 2.0 * d["d"] * d["dtype_bytes"],
        flops_per_unit=lambda d: 4.0 * d["d"],
        vmem_per_block=lambda d, b: 3 * b * d["d"] * d["dtype_bytes"],
        cap=lambda d: 4096)


def _register_decode_attention():
    from repro.kernels.decode_attention import (decode_attention_pallas,
                                                plan_cache_block)

    def describe(q, k_cache, v_cache, cache_len=None, **kwargs):
        return {"s": int(k_cache.shape[-2]), "d": int(k_cache.shape[-1]),
                "dtype": _dt(k_cache), "dtype_bytes": k_cache.dtype.itemsize}

    return _register_int_block(
        "decode_attention", describe,
        sig_shapes=lambda d: [(d["s"], d["d"])],
        seed_fn=lambda d, hw, pol: plan_cache_block(d["s"], d["d"], hw, pol,
                                                    d["dtype_bytes"]),
        run_with_block=lambda block, hw, interp, q, k, v, cache_len=None, **kw:
            decode_attention_pallas(q, k, v, cache_len, hw=hw, block_s=block,
                                    interpret=interp, **kw),
        quantum=128, lo=128,
        unit_count=lambda d: d["s"],
        bytes_per_unit=lambda d: 2.0 * d["d"] * d["dtype_bytes"],
        flops_per_unit=lambda d: 4.0 * d["d"],
        vmem_per_block=lambda d, b: 4 * b * max(d["d"], 128) * d["dtype_bytes"],
        cap=lambda d: 8192)


def _register_paged_decode():
    """The fused table-consuming paged decode sweep.  Not routable
    through ``_register_int_block``: its legality quantum is the TABLE
    geometry (``block_s`` must be whole physical pages), so the desc's
    ``page_block`` — not a registration constant — legalizes the value,
    and the geometry keys the signature (a different page size or table
    width is a different workload)."""
    from repro.kernels.paged_decode_attention import (paged_decode_attention,
                                                      plan_paged_block)

    def describe(q, k_cache, v_cache, tables, cache_len=None, *,
                 page_block, **kwargs):
        return {"s": int(k_cache.shape[1]), "d": int(k_cache.shape[-1]),
                "page_block": int(page_block),
                "max_blocks_per_row": int(tables.shape[-1]),
                "dtype": _dt(k_cache), "dtype_bytes": k_cache.dtype.itemsize}

    def sig(desc, policy):
        return workload_signature(
            "paged_decode", shapes=[(desc["s"], desc["d"])],
            dtypes=[desc["dtype"]], policy=policy,
            page_block=desc["page_block"],
            max_blocks_per_row=desc["max_blocks_per_row"])

    def _cap(desc):
        pb = desc["page_block"]
        return max(pb, min(8192 // pb * pb,
                           ceil_div(desc["s"], pb) * pb))

    def plan_from_value(desc, hw, value):
        pb = desc["page_block"]
        return _legal_int(int(value), pb, pb, _cap(desc))

    def seed_plan(desc, hw, policy):
        return plan_from_value(desc, hw, plan_paged_block(
            desc["s"], desc["d"], desc["page_block"], hw, policy,
            desc["dtype_bytes"]))

    def cost_model(desc, hw):
        s, db = desc["s"], desc["dtype_bytes"]
        d, dpad = desc["d"], max(desc["d"], 128)

        def cost(block):
            block = plan_from_value(desc, hw, block)
            if 4 * block * dpad * db > hw.vmem_budget_bytes:
                return _INF
            g = ceil_div(s, block)
            padded = g * block
            # k/v streamed once through the table — same bytes as the
            # gather-free dense sweep; one grid step per block_s chunk
            # (its pages are copied inside the step), so tiny blocks
            # lose on launches
            return (_roofline_s(padded * 4.0 * d, padded * 2.0 * d * db, hw)
                    + _launch_s(g, hw))

        return cost

    def candidates(desc, hw, seed_value):
        pb = desc["page_block"]
        return _scaled_candidates(int(seed_value), pb, pb, _cap(desc))

    def run(plan, hw, interpret, q, k_cache, v_cache, tables,
            cache_len=None, **kwargs):
        return paged_decode_attention(q, k_cache, v_cache, tables, cache_len,
                                      block_s=int(plan), interpret=interpret,
                                      **kwargs)

    return register_kernel(KernelSpec(
        name="paged_decode", describe=describe, sig=sig,
        seed_plan=seed_plan, plan_value=int,
        plan_from_value=plan_from_value, cost_model=cost_model,
        candidates=candidates, run=run))


def _register_stencil():
    from repro.kernels.stencil import gaussian_blur_pallas, plan_stencil_rows

    def describe(img, *, ksize=5, sigma=1.0, **kwargs):
        return {"h": int(img.shape[0]), "w": int(img.shape[1]),
                "ksize": int(ksize), "dtype": _dt(img),
                "dtype_bytes": img.dtype.itemsize}

    def halo(d):
        return (d["ksize"] - 1) // 2

    return _register_int_block(
        "gaussian_blur", describe,
        sig_shapes=lambda d: [(d["h"], d["w"])],
        seed_fn=lambda d, hw, pol: plan_stencil_rows(
            d["h"], d["w"], hw, pol, d["dtype_bytes"], halo(d)),
        run_with_block=lambda block, hw, interp, img, **kw:
            gaussian_blur_pallas(img, hw=hw, block_rows=block,
                                 interpret=interp, **kw),
        quantum=8, lo=8,
        unit_count=lambda d: d["h"],
        bytes_per_unit=lambda d: 4.0 * d["w"] * d["dtype_bytes"],
        flops_per_unit=lambda d: 4.0 * d["ksize"] * d["w"],
        vmem_per_block=lambda d, b: 4 * b * d["w"] * d["dtype_bytes"],
        extra_grid=lambda d: 2,                 # two passes
        cap=lambda d: None, extras=("ksize",))


def _register_gcn():
    from repro.kernels.gcn_agg import gcn_aggregate_pallas, plan_node_block

    def describe(adj_norm, feats, *, block_s=256, **kwargs):
        return {"n": int(adj_norm.shape[0]), "f": int(feats.shape[1]),
                "block_s": int(block_s), "dtype": _dt(feats),
                "dtype_bytes": feats.dtype.itemsize}

    return _register_int_block(
        "gcn_agg", describe,
        sig_shapes=lambda d: [(d["n"], d["n"]), (d["n"], d["f"])],
        seed_fn=lambda d, hw, pol: plan_node_block(d["n"], d["f"], hw, pol,
                                                   d["dtype_bytes"]),
        run_with_block=lambda block, hw, interp, adj, feats, **kw:
            gcn_aggregate_pallas(adj, feats, hw=hw, block_n=block,
                                 interpret=interp, **kw),
        quantum=8, lo=8,
        unit_count=lambda d: d["n"],
        # adjacency row + feature restream amortized + output row
        bytes_per_unit=lambda d: (d["n"] + 2.0 * d["f"]) * d["dtype_bytes"],
        flops_per_unit=lambda d: 2.0 * d["n"] * d["f"],
        vmem_per_block=lambda d, b: (b * d["block_s"] + b * max(d["f"], 128))
        * d["dtype_bytes"] * 2,
        extra_grid=lambda d: max(1, -(-d["n"] // d["block_s"])),
        cap=lambda d: 1024, extras=("block_s",))


def _register_nn_search():
    from repro.kernels.nn_search import nn_search_pallas, plan_query_block

    def describe(queries, refs, *, block_r=512, **kwargs):
        return {"nq": int(queries.shape[0]), "nr": int(refs.shape[0]),
                "d": int(queries.shape[1]), "block_r": int(block_r),
                "dtype": _dt(queries), "dtype_bytes": queries.dtype.itemsize}

    return _register_int_block(
        "nn_search", describe,
        sig_shapes=lambda d: [(d["nq"], d["d"]), (d["nr"], d["d"])],
        seed_fn=lambda d, hw, pol: plan_query_block(d["nq"], d["d"], hw, pol,
                                                    d["dtype_bytes"]),
        run_with_block=lambda block, hw, interp, q, r, **kw:
            nn_search_pallas(q, r, hw=hw, block_q=block, interpret=interp,
                             **kw),
        quantum=8, lo=8,
        # refs restreamed once per query block -> amortized per query row
        unit_count=lambda d: d["nq"],
        bytes_per_unit=lambda d: 2.0 * d["d"] * d["dtype_bytes"],
        flops_per_unit=lambda d: 3.0 * d["nr"] * d["d"],
        vmem_per_block=lambda d, b: 8 * b * max(d["d"], 128)
        * d["dtype_bytes"],
        extra_grid=lambda d: max(1, -(-d["nr"] // d["block_r"])),
        cap=lambda d: 2048, extras=("block_r",))


# --------------------------------------------------------------------------- #
# Mesh tier (plan-only: no Pallas call, no cost model -> clean fallback)
# --------------------------------------------------------------------------- #


def _register_mesh():
    def describe(**kwargs):
        return dict(kwargs)

    def sig(desc, policy):
        return workload_signature(
            "mesh_microbatch",
            shapes=[(desc["global_batch"],)], dtypes=["int32"],
            policy=policy, dp=desc["data_parallel"],
            act=round(desc["activation_bytes_per_seq"]),
            hbm=round(desc["hbm_budget_bytes"]))

    def seed_plan(desc, hw, policy):
        return plan_microbatch(desc["global_batch"], desc["data_parallel"],
                               desc["activation_bytes_per_seq"],
                               desc["hbm_budget_bytes"], policy=policy)

    def plan_from_value(desc, hw, value):
        # rebuild by re-planning — the decision is fully determined by the
        # signature inputs, so the cached value is corroboration only; if
        # planner logic evolved under an unchanged signature the fresh
        # plan wins (a stale entry must never be able to crash dispatch)
        del value
        return seed_plan(desc, hw, MappingPolicy.TUNED)

    return register_kernel(KernelSpec(
        name="mesh_microbatch", describe=describe, sig=sig,
        seed_plan=seed_plan,
        plan_value=lambda p: int(p.num_microbatches),
        plan_from_value=plan_from_value,
        cost_model=None,                      # exercised fallback path
        candidates=lambda d, hw, s: [s], run=None))


def resolve_mesh_plan(
    global_batch: int,
    data_parallel: int,
    activation_bytes_per_seq: float,
    hbm_budget_bytes: float,
    hw: Optional[TpuParams] = None,
    policy: MappingPolicy | str = MappingPolicy.AUTO,
    cache: Optional[TuningCache] = None,
) -> MeshPlan:
    """Mesh-tier entry used by ``launch.steps.resolve_microbatches``.

    Example::

        mesh_plan = resolve_mesh_plan(512, 8, act_bytes, hbm_budget)
    """
    desc = dict(global_batch=global_batch, data_parallel=data_parallel,
                activation_bytes_per_seq=activation_bytes_per_seq,
                hbm_budget_bytes=hbm_budget_bytes)
    hw = hw if hw is not None else detect()
    plan, _ = resolve_plan("mesh_microbatch", hw, policy, desc, cache)
    return plan


# --------------------------------------------------------------------------- #
# Populate the registry
# --------------------------------------------------------------------------- #


def _populate() -> None:
    from repro.kernels.saxpy import saxpy_pallas
    from repro.kernels.vecadd import vecadd_pallas

    _register_vector("vecadd", vecadd_workload, vecadd_pallas, n_arrays=3)
    _register_vector("saxpy", saxpy_workload, saxpy_pallas, n_arrays=3)
    _register_matmul()
    _register_flash_attention()
    _register_rmsnorm()
    _register_decode_attention()
    _register_paged_decode()
    _register_stencil()
    _register_gcn()
    _register_nn_search()
    _register_mesh()


_populate()
