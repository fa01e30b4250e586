"""Runtime-resolved sharding rules — Eq. 1 at the mesh tier.

Just as the paper's runtime reads (cores, warps, threads) and resolves the
lws mapping, this module reads (mesh shape, model config, input shape,
HBM budget) and resolves:

  * which logical param axes map to the ``model`` mesh axis (TP / EP),
    with divisibility-aware fallbacks (GQA heads that don't divide the TP
    degree fall back to head_dim sharding for caches / replication for
    weights);
  * whether FSDP over the data axes is required (param+state bytes vs the
    HBM budget — the memory-constrained regime);
  * activation rules (batch -> data axes, sequence-parallel residual
    stream, vocab-sharded logits, seq-sharded KV cache when batch < dp).

Everything is a pure function of static shapes, so it runs at trace time —
"without being explicitly specified by the programmer" (paper §2).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models.layers import ParamSpec, ShardCtx

PyTree = Any

#: default fraction of v5e HBM available for params+optimizer before FSDP
#: kicks in (leaves room for activations + caches)
FSDP_THRESHOLD_BYTES = 6 * 1024**3


def _is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


@dataclasses.dataclass(frozen=True)
class MeshInfo:
    mesh: Mesh
    data_axes: tuple[str, ...]     # ("pod", "data") or ("data",)
    model_axes: tuple[str, ...]    # ("model",)

    @property
    def dp(self) -> int:
        return math.prod(self.mesh.shape[a] for a in self.data_axes)

    @property
    def tp(self) -> int:
        return math.prod(self.mesh.shape[a] for a in self.model_axes)

    @property
    def n_devices(self) -> int:
        return self.dp * self.tp


def mesh_info(mesh: Mesh) -> MeshInfo:
    names = mesh.axis_names
    data = tuple(n for n in names if n in ("pod", "data"))
    model = tuple(n for n in names if n == "model")
    return MeshInfo(mesh, data, model)


# --------------------------------------------------------------------------- #
# Parameter rules
# --------------------------------------------------------------------------- #


@dataclasses.dataclass
class Plan:
    """The resolved distribution plan for one (config, mesh, shape) cell."""

    info: MeshInfo
    param_rules: dict[str, Optional[Any]]
    act_rules: dict[str, Optional[Any]]
    fsdp: bool
    zero1: bool
    kv_mode: str                     # "grouped" | "expand" | "replicated"
    # runtime memory-regime decisions (Eq. 1's memory tier): dtypes of the
    # grad accumulator and Adam moments, degraded only when f32 can't fit
    accum_dtype: str = "float32"
    moment_dtype: str = "float32"
    cache_dtype: str = "default"     # any repro.core.dtypes spelling
    notes: list[str] = dataclasses.field(default_factory=list)

    @property
    def kv_spec(self):
        """The shared KV dtype descriptor (``repro.core.dtypes``) this
        plan's ``cache_dtype`` string resolves to — the same vocabulary
        the serving pool and ``launch/dryrun.py`` use."""
        from repro.core.dtypes import kv_dtype_spec

        return kv_dtype_spec(self.cache_dtype)

    @property
    def cache_dtype_bytes(self) -> Optional[int]:
        return self.kv_spec.bytes

    @property
    def expand_kv(self) -> bool:
        return self.kv_mode == "expand"


def resolve_plan(
    cfg: ModelConfig,
    mesh: Mesh,
    shape: Optional[ShapeConfig] = None,
    *,
    fsdp_threshold: float = FSDP_THRESHOLD_BYTES,
    zero1: bool = True,
    sequence_parallel: bool = True,
) -> Plan:
    """The runtime mapping decision (paper Eq. 1 generalized)."""
    info = mesh_info(mesh)
    tp, dp = info.tp, info.dp
    m = info.model_axes[0] if info.model_axes else None
    notes = []

    def div(n: int) -> Optional[str]:
        return m if (m and n % tp == 0) else None

    param_rules: dict[str, Optional[Any]] = {
        "vocab": div(cfg.vocab_size),
        "embed": None,
        "heads": div(max(cfg.num_heads, 1)),
        "kv_heads": div(max(cfg.num_kv_heads, 1)),
        "head_dim": None,
        "mlp": None,     # filled below (depends on which ff dim exists)
        "experts": div(max(cfg.moe_experts, 1)) if cfg.moe_experts else None,
        "experts_r": None,
        "inner": None,
        "conv": None,
        "layers": None,
    }
    ffs = [x for x in (cfg.d_ff, cfg.moe_shared_experts * cfg.moe_dff)
           if x > 0]
    param_rules["mlp"] = m if (m and all(f % tp == 0 for f in ffs)) else None
    if cfg.family in ("ssm", "hybrid"):
        di = cfg.d_inner
        conv_ch = di + 2 * cfg.ssm_groups * cfg.ssm_state
        inner_dims = [2 * di + 2 * cfg.ssm_groups * cfg.ssm_state
                      + cfg.ssm_heads, conv_ch, di]
        param_rules["inner"] = m if (m and all(x % tp == 0 for x in inner_dims)) \
            else None
    if param_rules["heads"] is None and cfg.num_heads:
        notes.append(f"heads={cfg.num_heads} % tp={tp} != 0 -> attn weights "
                     "replicated over model axis")
    # GQA regime: grouped (kv divisible) > expand-kv (heads divisible) >
    # replicated — resolved at runtime from (config, mesh)
    if not cfg.num_kv_heads:
        kv_mode = "grouped"
    elif param_rules["kv_heads"] is not None:
        kv_mode = "grouped"
    elif param_rules["heads"] is not None:
        kv_mode = "expand"
        notes.append(f"kv_heads={cfg.num_kv_heads} % tp={tp} != 0 -> "
                     "KV expanded to full heads, head-sharded "
                     f"({cfg.num_heads // cfg.num_kv_heads}x duplication, "
                     f"{cfg.num_heads // tp} head copies/device)")
    else:
        kv_mode = "replicated"
        notes.append("attention fully replicated (heads and kv_heads both "
                     f"indivisible by tp={tp})")

    # ---- FSDP decision (memory regime of Eq. 1) ------------------------ #
    bytes_per_param = 2 if cfg.dtype == "bfloat16" else 4
    n_params = cfg.n_params()
    # params + grads (same dtype) + adam m,v in f32 under zero1
    state_bytes = n_params * bytes_per_param * 2 / tp \
        + (n_params * 8 / (tp * dp) if zero1 else n_params * 8 / tp)
    fsdp = state_bytes > fsdp_threshold or \
        (n_params * bytes_per_param * 2 / tp) > fsdp_threshold
    if fsdp:
        notes.append(
            f"params+grads {n_params * bytes_per_param * 2 / tp / 1e9:.1f}GB/dev "
            f"over model axis alone -> FSDP over {info.data_axes}")

    # ---- state-dtype decision (same memory model, next regime down) ---- #
    world = tp * dp
    accum_dtype, moment_dtype = "float32", "float32"
    if shape is not None and shape.kind == "train":
        hbm = 16 * 1024**3
        fully_sharded = world if fsdp or zero1 else tp
        budget_used = (
            n_params * bytes_per_param / (world if fsdp else tp)   # params
            # grad accumulation holds TWO live copies (carry + incoming)
            + 2 * n_params * 4 / (world if fsdp else tp)           # f32 grads
            + n_params * 8 / fully_sharded                         # m+v f32
        )
        if budget_used > 0.7 * hbm:
            moment_dtype = "bfloat16"
            notes.append("f32 Adam moments would exceed HBM -> bf16 moments")
            budget_used -= n_params * 4 / fully_sharded
        if budget_used > 0.7 * hbm:
            accum_dtype = "bfloat16"
            notes.append("f32 grad accumulator would exceed HBM -> bf16")

    # ---- activation rules ---------------------------------------------- #
    da: Any = info.data_axes if len(info.data_axes) > 1 else \
        (info.data_axes[0] if info.data_axes else None)
    batch_ok = shape is None or shape.global_batch % max(dp, 1) == 0
    seq = shape.seq_len if shape else 0
    act_rules: dict[str, Optional[Any]] = {
        "batch": da if (da and batch_ok and
                        (shape is None or shape.global_batch >= dp)) else None,
        "seq_sp": (m if (sequence_parallel and m and shape is not None
                         and shape.kind != "decode" and seq % tp == 0)
                   else None),
        "heads": param_rules["heads"],
        "kv_heads": param_rules["kv_heads"],
        "mlp": param_rules["mlp"],
        "experts": param_rules["experts"],
        "inner": param_rules["inner"],
        "vocab": param_rules["vocab"],
        "embed": None,
    }
    act_rules["cache_seq"] = None
    if shape is not None and shape.kind in ("decode", "prefill"):
        if act_rules["batch"] is None and shape.kind == "decode":
            # batch too small to shard -> shard the KV-cache sequence over
            # the data axes instead (distributed flash-decode; long_500k)
            act_rules["cache_seq"] = da
            notes.append("batch < dp -> KV cache sequence-sharded over "
                         "data axes")
        elif cfg.num_kv_heads and m is not None:
            # Eq.1's memory tier for the cache: compare per-device cache
            # bytes under (a) head sharding (grouped/expand/replicated)
            # vs (b) sequence sharding over the model axis with kv heads
            # replicated; pick (b) when it is a >=2x win and T divides.
            db2 = 2 if cfg.dtype == "bfloat16" else 4
            if cfg.family == "hybrid":
                n_attn = -(-cfg.num_layers // cfg.hybrid_attn_every)
            else:
                n_attn = cfg.num_layers
            b_dev = shape.global_batch // max(dp, 1)
            kvh = cfg.num_kv_heads
            g_eff = (cfg.num_heads / tp if kv_mode == "expand"
                     else (kvh / tp if kv_mode == "grouped" and kvh % tp == 0
                           else kvh))
            head_mode = 2 * n_attn * b_dev * shape.seq_len * g_eff \
                * cfg.head_dim * db2
            seq_mode = 2 * n_attn * b_dev * (shape.seq_len / tp) * kvh \
                * cfg.head_dim * db2
            if shape.seq_len % tp == 0 and seq_mode * 2 <= head_mode:
                act_rules["cache_seq"] = m
                kv_mode = "replicated"      # kv heads whole on each shard
                notes.append(
                    f"cache {head_mode/2**30:.1f}GB/dev head-sharded -> "
                    f"{seq_mode/2**30:.1f}GB/dev sequence-sharded over "
                    "model axis (split-KV decode)")
    # MoE group-local routing: groups aligned with the data shards
    act_rules["moe_group"] = act_rules["batch"]

    return Plan(info=info, param_rules=param_rules, act_rules=act_rules,
                fsdp=fsdp, zero1=zero1, kv_mode=kv_mode,
                accum_dtype=accum_dtype, moment_dtype=moment_dtype,
                notes=notes)


def choose_serve_mesh(cfg: ModelConfig, n_chips: int = 256,
                      budget: float = 12 * 1024**3) -> tuple[int, int]:
    """Pick the (dp, tp) factorization for SERVING so that model-sharded
    weights fit HBM without FSDP (per-layer weight gathers every decode
    step are the decode killer).  Eq. 1 applied to the mesh shape itself:
    tp = smallest power of two with params/tp <= budget."""
    db = 2 if cfg.dtype == "bfloat16" else 4
    n = cfg.n_params() * db
    tp = 1
    while n / tp > budget and tp < n_chips:
        tp *= 2
    # keep tp no smaller than the heads-divisibility sweet spot
    dp = max(n_chips // tp, 1)
    return dp, tp


def make_serve_mesh(cfg: ModelConfig, n_chips: int = 256):
    dp, tp = choose_serve_mesh(cfg, n_chips)
    return jax.make_mesh((dp, tp), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def param_pspec(spec: ParamSpec, plan: Plan) -> P:
    """Logical axes -> PartitionSpec, with optional FSDP second pass."""
    assigned = [plan.param_rules.get(a) if a else None for a in spec.axes]
    if plan.fsdp:
        dp_total = plan.info.dp
        # shard the largest still-unsharded dim divisible by dp
        order = sorted(range(len(spec.shape)),
                       key=lambda i: -spec.shape[i])
        for i in order:
            if assigned[i] is None and spec.axes[i] != "layers" \
                    and spec.shape[i] % max(dp_total, 1) == 0 and dp_total > 1:
                assigned[i] = (plan.info.data_axes
                               if len(plan.info.data_axes) > 1
                               else plan.info.data_axes[0])
                break
    return P(*assigned)


def param_shardings(specs: PyTree, plan: Plan) -> PyTree:
    return jax.tree.map(
        lambda s: NamedSharding(plan.info.mesh, param_pspec(s, plan)),
        specs, is_leaf=_is_spec)


def zero1_pspec(spec: ParamSpec, plan: Plan) -> P:
    """Optimizer-state sharding: param sharding + data-axis sharding on the
    largest remaining dim (ZeRO-1).  No-ops when FSDP already consumed it."""
    base = list(param_pspec(spec, plan))
    base += [None] * (len(spec.shape) - len(base))
    if not plan.zero1:
        return P(*base)
    dp_total = plan.info.dp
    used = set()
    for b in base:
        for ax in (b if isinstance(b, tuple) else (b,)):
            used.add(ax)
    if any(a in used for a in plan.info.data_axes):
        return P(*base)       # FSDP already shards over data
    order = sorted(range(len(spec.shape)), key=lambda i: -spec.shape[i])
    for i in order:
        if base[i] is None and spec.shape[i] % max(dp_total, 1) == 0 \
                and dp_total > 1:
            base[i] = (plan.info.data_axes if len(plan.info.data_axes) > 1
                       else plan.info.data_axes[0])
            break
    return P(*base)


def zero1_shardings(specs: PyTree, plan: Plan) -> PyTree:
    return jax.tree.map(
        lambda s: NamedSharding(plan.info.mesh, zero1_pspec(s, plan)),
        specs, is_leaf=_is_spec)


# --------------------------------------------------------------------------- #
# Batch + cache shardings
# --------------------------------------------------------------------------- #


def batch_pspec(plan: Plan) -> P:
    return P(plan.act_rules["batch"])


def batch_shardings(batch_specs: dict, plan: Plan) -> dict:
    """Shard every batch leaf on its leading (batch) dim."""
    b = plan.act_rules["batch"]

    def shard(leaf):
        ndim = len(leaf.shape)
        return NamedSharding(plan.info.mesh, P(b, *([None] * (ndim - 1))))

    return jax.tree.map(shard, batch_specs)


def cache_pspec(plan: Plan, cfg: ModelConfig, kind: str) -> P:
    """PartitionSpec for one KV-cache leaf (L, B, T, G, hd) or SSM state."""
    b = plan.act_rules["batch"]
    t = plan.act_rules.get("cache_seq")
    if kind == "kv":
        g = (plan.param_rules["heads"] if plan.expand_kv
             else plan.param_rules["kv_heads"])
        return P(None, b, t, g, None)
    if kind == "ssm_state":                 # (L, B, H, N, P)
        return P(None, b, plan.param_rules["inner"], None, None)
    if kind == "ssm_conv":                  # (L, B, K-1, C)
        return P(None, b, None, plan.param_rules["inner"])
    if kind == "scalar":
        return P()
    raise ValueError(kind)


def cache_shardings(cache_specs: dict, plan: Plan, cfg: ModelConfig) -> dict:
    out = {}
    for name, leaf in cache_specs.items():
        if name in ("k", "v", "ck", "cv"):
            kind = "kv"
        elif name == "state":
            kind = "ssm_state"
        elif name == "conv":
            kind = "ssm_conv"
        else:
            kind = "scalar"
        ps = cache_pspec(plan, cfg, kind)
        out[name] = NamedSharding(plan.info.mesh, ps)
    return out


def make_ctx(plan: Plan) -> ShardCtx:
    return ShardCtx(plan.act_rules, mesh=plan.info.mesh,
                    flags={"expand_kv": plan.expand_kv,
                           "moe_groups": plan.info.dp})
