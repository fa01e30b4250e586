"""Continuous-batching serving engine.

The engine interleaves prefill and decode over a live request pool:

  * admitted requests prefill individually (prompt padded to the length
    the family's ``CacheAdapter`` asks for, true-last-token logits via
    ``Model.prefill(last_pos=...)``) and their primed cache rows are
    written into the pool at the leased slot;
  * the whole pool decodes one token per tick through ONE compiled step
    whose rows are ragged — every row carries its own position
    (``cache["pos"]`` is a vector; see ``models.attention``), so a slot
    that just admitted a 7-token prompt coexists with one 900 tokens
    into its answer;
  * finished requests retire mid-decode: their slot + KV blocks recycle
    to the queue head on the next tick (``scheduler``), so steady-state
    utilization stays near 1 while shapes — and therefore the tuned
    kernel mappings — are managed by the bucket lattice (``buckets``).

The pool is family-generic: a ``CacheAdapter`` (``adapters``) owns the
per-family cache state — init / row writes / growth over per-row
positions — so dense, MoE, SSM, hybrid, and encoder-decoder models all
ride the same ragged pool through one interface.

Geometry changes (pool-length bucket steps) are the runtime events the
paper's thesis is about: each one re-routes through ``tuner.resolve_plan``
for the new bucket's kernel plans and triggers at most one new XLA
compile, bounded by the lattice.  The resolved plan is not just recorded:
its ``decode_block`` is threaded into the jitted decode step as a static
argument, so the bucket decision selects the attention sweep that
actually executes (``models.attention.attention_decode``).

The engine's clock is injectable; when the pool is idle it fast-forwards
to the next synthetic arrival, so open-loop traffic with sparse arrivals
never sleeps the process (virtual-time simulation, standard for
device-free benchmarks).
"""

from __future__ import annotations

import dataclasses
import gc
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ShapeConfig, get_config
from repro.core.hw import TpuParams
from repro.core.mapper import MappingPolicy
from repro.launch.mesh import make_local_mesh
from repro.launch.steps import (make_chunk_prefill_step, make_decode_step,
                                make_prefill_step)
from repro.models import build_model
from repro.obs.trace import get_tracer
from repro.runtime import sharding as shd
from repro.serve.adapters import get_adapter
from repro.core.dtypes import kv_dtype_spec
from repro.serve.buckets import BucketRouter, BucketSpec
from repro.serve.kvcache import KVCachePool
from repro.serve.metrics import ServeMetrics, ServeSummary
from repro.serve.radix import RadixCache
from repro.serve.retune import RetuneConfig, RetuneController
from repro.serve.scheduler import Request, Scheduler
from repro.tuner import TuningCache

__all__ = ["ServeEngine", "ServeReport"]

#: the most (token, expert) pairs one "auto" prefill chunk of a model
#: with experts routes: 256 tokens at top-8
CHUNK_PAIRS = 2048


def _expert_load_attrs(load) -> dict:
    """Span attributes of one step's expert load (L, held): routed pairs
    on held experts summed over layers, held experts with at least one
    pair summed over layers, and the most pairs on one held expert."""
    load = np.asarray(load)
    return {"moe_routed": int(load.sum()),
            "moe_touched": int((load > 0).sum()),
            "moe_max_load": int(load.max(initial=0))}


@dataclasses.dataclass
class _ChunkTask:
    """One in-flight chunked prefill: a request whose prompt advances
    chunk-by-chunk between decode ticks instead of stalling the pool.
    The request holds its leased slot/blocks from admission, but decode
    skips it until ``write_row`` lands the finished row."""

    req: Request
    cache: Any                     # private B=1 row cache (length pb)
    toks: np.ndarray               # (prompt_len,) prompt tokens
    pb: int                        # row-cache length (prompt bucket)
    tiles: Optional[tuple]         # tuned flash tiles (static jit arg)
    chunk: int                     # chunk width C (static by shape)
    blocks: Optional[list] = None  # leased block ids (paged pools)
    done: int = 0                  # prompt tokens consumed so far
    #: first prompt position write_row scatters (block-aligned; the
    #: positions before it live in radix-SHARED blocks, never rewritten)
    start: int = 0


@dataclasses.dataclass
class ServeReport:
    """Everything one engine run produced.

    Example::

        report = engine.run()
        print(report.summary.tokens_per_s, report.outputs)
    """

    summary: ServeSummary
    outputs: dict[int, list[int]]          # rid -> prompt + generated
    completed: list[Request]
    rejected: list[Request]
    router_stats: dict
    compiled_decode_shapes: int
    compiled_prefill_shapes: int
    pool_growths: int
    #: distinct chunked-prefill compilations (C, cache_len, tiles) — the
    #: bounded set chunking buys for exact-length families (0 when off)
    compiled_chunk_shapes: int = 0
    #: retune controller accounting + concluded swap decisions
    #: (``None`` when the engine runs with ``retune="off"``)
    retune: Optional[dict] = None
    #: radix prefix-cache accounting (hit rate, evictions; ``None`` when
    #: ``prefix_cache=False`` or the family cannot share prefixes)
    radix: Optional[dict] = None


class ServeEngine:
    """Continuous-batching loop over a bucketed, tuned decode pool.

    ``arch`` is a registered config name or a ready ``ModelConfig``.
    ``reduced`` applies only to names — a ``ModelConfig`` is served
    exactly as given (callers shrinking a config do it explicitly, e.g.
    ``get_config(n).reduced()``).

    ``paged=True`` (the default) makes KV paging PHYSICAL: each lease's
    block ids become an indirection table threaded into the decode step,
    writes scatter into leased blocks, and admission after recycling
    re-points blocks instead of copying cache rows.  The decode read is
    FUSED by default — the tables ride into
    ``kernels.paged_decode_attention`` as data operands at the router's
    tuned ``block_s`` — and ``fused_decode=False`` falls back to
    gather-then-sweep (the fused-vs-gather ablation
    ``benchmarks/serve_bench.py`` measures).  ``paged=False`` keeps the
    contiguous row layout; note paged mode requires ``max_len`` (and
    every lattice length) to be a multiple of ``block_size``.
    ``use_prefill_tiles=False`` drops the bucket-tuned prefill flash
    tiles back to the GSPMD path (the tuned-vs-default ablation
    ``benchmarks/serve_bench.py`` measures).

    ``tracer`` threads an ``obs.Tracer`` through the whole runtime:
    every loop iteration is a ``step`` span with its phases nested
    (admit, prefill chunk, decode tick, device wait, sample, retire,
    report), every prefill admit and decode tick carries its bucket key
    and executed plan, router/tuner resolutions record their
    provenance, and pool growth / slot recycling emit instants; while
    the JAX profiler records, the spans also appear in its trace as
    ``serve.<name>`` — see docs/OBSERVABILITY.md.  ``None`` binds the
    ambient tracer at construction time (``obs.trace.get_tracer()``, the
    null tracer by default), so an untraced engine pays constant no-ops
    and its jitted steps lower to byte-identical HLO
    (``tests/test_obs.py`` pins this).

    Example::

        eng = ServeEngine("smollm-135m", slots=4, max_len=256)
        eng.submit([1, 2, 3], max_new_tokens=8)
        report = eng.run()
    """

    def __init__(self, arch: str | ModelConfig, *,
                 slots: int = 4,
                 max_len: int = 256,
                 reduced: bool = True,
                 spec: Optional[BucketSpec] = None,
                 admission: str = "continuous",
                 policy: MappingPolicy | str = MappingPolicy.TUNED,
                 measure: str = "off",
                 store: Optional[Any] = None,
                 tuning_cache: Optional[TuningCache] = None,
                 hw: Optional[TpuParams] = None,
                 mesh=None,
                 params=None,
                 block_size: int = 16,
                 total_blocks: Optional[int] = None,
                 paged: bool = True,
                 kv_dtype: str = "fp32",
                 fused_decode: bool = True,
                 use_prefill_tiles: bool = True,
                 eos_id: Optional[int] = None,
                 clock: Callable[[], float] = time.monotonic,
                 tracer: Optional[Any] = None,
                 retune: str | RetuneConfig | None = "off",
                 prefill_chunk: int | str | None = "auto",
                 prefix_cache: bool = False,
                 verbose: bool = False):
        cfg = get_config(arch) if isinstance(arch, str) else arch
        if isinstance(arch, str) and reduced:
            cfg = cfg.reduced()
        # one registry lookup decides serveability (raises for families
        # with no adapter); the adapter also carries the family's cache
        # position offset (vlm's patch prefix) and whether its paged
        # blocks are complete per-position context (radix sharing)
        self.adapter = get_adapter(cfg.family)
        self.cfg = cfg
        self.slots = slots
        self.spec = spec or BucketSpec(max_len=max_len,
                                       min_len=min(32, max_len))
        if self.spec.max_len > max_len:
            self.spec = dataclasses.replace(
                self.spec, max_len=max_len,
                min_len=min(self.spec.min_len, max_len))
        self.eos_id = eos_id
        self.verbose = verbose
        self._clock = clock
        self._t0: Optional[float] = None
        self._skew = 0.0
        self.obs = tracer if tracer is not None else get_tracer()
        self._retune_cfg: Optional[RetuneConfig] = None
        if retune not in (None, "off"):
            self._retune_cfg = retune if isinstance(retune, RetuneConfig) \
                else RetuneConfig(mode=retune)
            if not self.obs.enabled:
                # the controller's drift scan reads spans; a retuning
                # engine with no tracer gets a private one (host-side
                # only — the compiled steps are unaffected)
                from repro.obs.trace import Tracer
                self.obs = Tracer()

        self.model = build_model(cfg)
        self.mesh = mesh if mesh is not None else make_local_mesh(1, 1)
        shape = ShapeConfig("serve", self.spec.max_len, slots, "decode")
        self.plan = shd.resolve_plan(cfg, self.mesh, shape)
        self.params = params if params is not None \
            else self.model.init(jax.random.key(0))

        # pool storage dtype: "fp32" keeps today's bit-exact pool (and
        # lowers byte-identical HLO); "int8" stores symmetric per-(block,
        # head) codes + scales and requires the paged layout (scales are
        # keyed on physical blocks)
        self.kv_spec = kv_dtype_spec(kv_dtype)
        if self.kv_spec.quantized and not paged:
            raise ValueError(
                f"kv_dtype={self.kv_spec.name!r} requires paged=True: "
                "quantization scales are per physical block")
        self.router = BucketRouter(cfg, self.spec, slots=slots, hw=hw,
                                   policy=policy, cache=tuning_cache,
                                   measure=measure, store=store,
                                   page_block=block_size if paged else None,
                                   kv_dtype=self.kv_spec.name,
                                   tracer=self.obs)
        self._block_size = block_size
        self._total_blocks = total_blocks
        self._admission = admission
        self.paged = paged
        self.fused_decode = fused_decode
        self.use_prefill_tiles = use_prefill_tiles
        kv0 = self.spec.quantize(1)
        if paged:
            # the physical grid maps block ids onto (slot, offset) pairs:
            # EVERY lattice length must be whole blocks (a non-multiple
            # would only surface at the mid-run growth that hits it), and
            # the budget may undersubscribe the grid (admission control)
            # but never exceed it (ids past the grid have no location)
            lattice = self.spec.lattice()
            if not lattice:          # "exact" mode: unbounded lengths
                raise ValueError(
                    "paged mode needs a finite length lattice; "
                    "mode='exact' cannot guarantee block-multiple rows")
            for n in lattice:
                if n % block_size:
                    raise ValueError(
                        f"paged mode needs lattice lengths divisible by "
                        f"block_size={block_size}, got {n}")
            cap0 = slots * (kv0 // block_size)
            if total_blocks is not None and total_blocks > cap0:
                raise ValueError(
                    f"paged mode: total_blocks={total_blocks} exceeds the "
                    f"physical block grid ({cap0})")
        #: chunked prefill: "auto" (the default) derives the chunk width
        #: from the tuned flash tiles (block_q — prefill advances in the
        #: tile quanta the tuner chose); an int fixes the width; None
        #: opts back out to whole-prompt prefill
        if prefill_chunk is not None and not isinstance(prefill_chunk, int) \
                and prefill_chunk != "auto":
            raise ValueError(f"prefill_chunk must be None, an int, or "
                             f"'auto', got {prefill_chunk!r}")
        self._chunk_cfg = prefill_chunk
        self._chunked = (prefill_chunk is not None
                         and self.model.supports_chunked_prefill)
        #: cache positions before token 0 (vlm's patch prefix): every
        #: capacity/page-map/position computation adds it
        self._pos_offset = self.adapter.position_offset(self.model)
        self.prefix_cache = bool(prefix_cache)
        self.pool = KVCachePool(slots, kv0, block_size=block_size,
                                total_blocks=total_blocks,
                                max_len=self.spec.max_len,
                                kv_dtype=self.kv_spec.name)
        self._radix = self._make_radix()
        self.scheduler = Scheduler(self.pool, mode=admission,
                                   radix=self._radix,
                                   pos_offset=self._pos_offset)
        self.metrics = ServeMetrics()
        self.outputs: dict[int, list[int]] = {}

        # prefill_tiles is static: a new tile pair is a new prompt
        # bucket, and bucket steps are the (lattice-bounded) compile
        # events; same for decode_block / page_block on the decode side
        self._prefill = jax.jit(make_prefill_step(self.model, self.plan, None),
                                static_argnames=("prefill_tiles", "pad_to"))
        self._decode = jax.jit(make_decode_step(self.model, self.plan),
                               static_argnames=("decode_block",
                                                "page_block",
                                                "paged_decode_block"))
        # one program per write shape, the pool donated: the row lands in
        # place and the pool is never held twice
        self._write_row = jax.jit(self.adapter.write_row,
                                  static_argnames=("prompt_len", "kv_len",
                                                   "page_block", "start"),
                                  donate_argnames=("cache",))
        self._chunk_step = jax.jit(
            make_chunk_prefill_step(self.model, self.plan),
            static_argnames=("prefill_tiles",))
        self._chunk_tasks: list[_ChunkTask] = []
        self._prefilling: dict[int, _ChunkTask] = {}      # rid -> task
        self.compiled_chunk_shapes: set[tuple] = set()

        self.retune: Optional[RetuneController] = None
        if self._retune_cfg is not None:
            self.retune = RetuneController(self.router,
                                           config=self._retune_cfg,
                                           tracer=self.obs, store=store,
                                           cache=tuning_cache)
        self._cache = self.adapter.init_pool(self.model, slots, kv0,
                                             expand_kv=self.plan.expand_kv,
                                             kv_dtype=self.kv_spec.name,
                                             block_size=block_size)
        self._tables = np.full((slots, self.pool.max_blocks_per_row), -1,
                               np.int32)
        self._tables_dev = None      # device-array memo (tables are data
        #                              but change only at admit/retire)
        self._tokens = np.zeros((slots, 1), np.int32)
        #: moe: the steps also return the expert layer's load, and the
        #: decode step is told which rows are in flight (row written,
        #: not yet retired) so that idle rows add no expert work
        self._experts = cfg.family == "moe"
        self._in_flight = np.zeros(slots, bool)
        self._in_flight_dev = None
        self._plan_len = -1                  # _current_plan memo key
        self._bucket_plan = None
        self.compiled_decode_shapes: set[tuple[int, int]] = set()
        self.compiled_prefill_shapes: set[int] = set()
        self.pool_growths = 0

        if self.obs.enabled:
            # run-level context the trace exporters embed in the header —
            # everything obs.feedback/obs.drift need to rebuild each
            # bucket's tuner workload desc offline from the trace alone
            self.obs.meta.update(
                arch=cfg.name, family=cfg.family,
                head_dim=cfg.head_dim,
                kv_heads=max(cfg.num_kv_heads, 1),
                layers=cfg.num_layers, dtype=cfg.dtype,
                dtype_bytes=self.router._dtype_bytes(),
                slots=slots, max_len=self.spec.max_len,
                hw=self.router.hw.name, paged=paged,
                fused_decode=fused_decode,
                kv_dtype=self.kv_spec.name,
                prefix_cache=self._radix is not None,
                **(self.router._geometry() or {}))

    def _make_radix(self) -> Optional[RadixCache]:
        """A fresh radix prefix cache over the CURRENT pool's allocator
        — or ``None`` when sharing cannot engage: the feature is off,
        the pool is not physically paged (no tables to alias through),
        prefill is not chunked (no mid-prompt resume), or the family's
        blocks are not complete per-position context
        (``adapter.shareable_prefix``).  A ``prefix_cache=True`` engine
        on a non-shareable family still serves correctly — lookups
        simply never run (hit rate 0)."""
        if not (self.prefix_cache and self.paged and self._chunked
                and getattr(self.adapter, "shareable_prefix", False)):
            return None
        return RadixCache(self.pool.allocator, self._block_size,
                          tracer=self.obs)

    def reset(self) -> None:
        """Clear traffic state but KEEP the warm machinery — jitted
        steps, resolved bucket plans, the tuning cache, and the
        compile-shape history.  Callers reuse one engine across traffic
        mixes; benchmarks use it to separate steady-state behaviour from
        cold-start compiles."""
        kv0 = self.spec.quantize(1)
        self.pool = KVCachePool(self.slots, kv0,
                                block_size=self._block_size,
                                total_blocks=self._total_blocks,
                                max_len=self.spec.max_len,
                                kv_dtype=self.kv_spec.name)
        self._radix = self._make_radix()
        self.scheduler = Scheduler(self.pool, mode=self._admission,
                                   radix=self._radix,
                                   pos_offset=self._pos_offset)
        self.metrics = ServeMetrics()
        self.outputs = {}
        self._cache = self.adapter.init_pool(self.model, self.slots, kv0,
                                             expand_kv=self.plan.expand_kv,
                                             kv_dtype=self.kv_spec.name,
                                             block_size=self._block_size)
        self._tables = np.full((self.slots, self.pool.max_blocks_per_row),
                               -1, np.int32)
        self._tables_dev = None
        self._tokens = np.zeros((self.slots, 1), np.int32)
        self._in_flight = np.zeros(self.slots, bool)
        self._in_flight_dev = None
        self.pool_growths = 0
        self._t0 = None
        self._skew = 0.0
        self._chunk_tasks = []
        self._prefilling = {}
        # what is live now (jitted steps, plans, weights, the tuning
        # cache) lives as long as the engine: move it out of the cyclic
        # collector's reach, so that a full collection while serving
        # walks only what serving allocated since; one over the whole
        # warm heap takes tens of ms, all inside one engine step.
        gc.collect()
        gc.freeze()

    # -- time -------------------------------------------------------------

    def _now(self) -> float:
        if self._t0 is None:
            self._t0 = self._clock()
        return self._clock() - self._t0 + self._skew

    def _fast_forward(self, to_t: float) -> None:
        now = self._now()
        if to_t > now:
            self._skew += to_t - now

    # -- pool plumbing ----------------------------------------------------

    def _decode_shape(self) -> tuple[int, int]:
        """The compiled decode geometry.  Length-free caches (ssm) keep
        ONE decode shape however far the accounting pool grows."""
        kv = self.pool.kv_len if self.adapter.grows_with_len else 0
        return (self.slots, kv)

    def _current_plan(self):
        """The live bucket's resolved plan, memoized on the pool length
        so the per-token decode loop pays an int compare — not a
        signature build — and RouterStats keeps counting bucket
        resolutions, not decode ticks."""
        if self._plan_len != self.pool.kv_len:
            self._bucket_plan = self.router.resolve(
                self.router.bucket(self.pool.kv_len))
            self._plan_len = self.pool.kv_len
        return self._bucket_plan

    def _grow_pool(self, new_len: int) -> None:
        if self.paged and new_len % self._block_size:
            raise ValueError(f"paged pool length {new_len} not a multiple "
                             f"of block_size={self._block_size}")
        self._cache = self.adapter.grow(self._cache, new_len) \
            if self.adapter.grows_with_len else self._cache
        self.pool.grow(new_len)
        self.pool_growths += 1
        self.obs.instant("pool_grow", kv_len=new_len)
        self.obs.count("pool_growths")
        if self.verbose:
            print(f"[serve] pool -> ({self.slots}, {new_len})")

    def _page_map(self, blocks: list[int], n: int,
                  start: int = 0) -> jax.Array:
        """Flat physical positions of one request's logical tokens
        ``[start, n)`` (the prefill write path; ``kernels.paged_gather``
        documents the pid -> location mapping).  ``start`` skips the
        radix-shared prefix — positions another lease already wrote and
        this one must never scatter into."""
        from repro.kernels.paged_gather import flat_position

        bs = self._block_size
        tok = np.arange(start, n)
        pid = np.asarray(blocks, np.int64)[tok // bs]
        return jnp.asarray(
            flat_position(pid, tok, self.slots, self.pool.kv_len, bs),
            jnp.int32)

    def _scale_map(self, blocks: list[int]) -> np.ndarray:
        """Flat scale-array indices of one request's leased blocks: the
        scale grid is the cache's physical block grid flattened to
        (slots * blocks_per_row), so pid -> (pid % slots) * nb + pid //
        slots — the same identity the fused kernels resolve in-sweep."""
        nb = self.pool.kv_len // self._block_size
        pid = np.asarray(blocks, np.int64)
        return ((pid % self.slots) * nb + pid // self.slots).astype(np.int32)

    # -- intake -----------------------------------------------------------

    def submit(self, req: Request | list[int], *,
               max_new_tokens: int = 16, arrival: float = 0.0) -> Request:
        """Queue a request (a ``Request`` or a raw prompt token list)."""
        if not isinstance(req, Request):
            req = Request(prompt=list(req), max_new_tokens=max_new_tokens,
                          arrival=arrival)
        req.prompt = [int(t) for t in req.prompt]
        if req.prompt_len < 1:
            raise ValueError("empty prompt")
        # never-seatable rejection (projected length over the pool's max
        # bucket) lives in ONE place: the scheduler; it marks
        # ``req.rejected`` so callers (traffic.drive) can react
        if self.scheduler.submit(req):
            self.metrics.on_submit(req.rid, req.arrival, req.prompt_len)
        return req

    # -- admission + prefill ----------------------------------------------

    def _admit(self, req: Request, now: float) -> None:
        if self._chunked:
            self._admit_chunked(req, now)
            return
        # the family's cache-position offset (vlm: prefix_tokens image
        # patches before token 0) shifts EVERY cache position: the
        # prompt bucket covers offset + prompt, the cache row pads to
        # offset + bucket (``pad_to``), and the final-token logits sit
        # at sequence position offset + prompt_len - 1
        off = self._pos_offset
        plen = req.prompt_len
        pb = self.adapter.prefill_len(off + plen,
                                      self.router.quantize_prompt) - off
        toks = np.zeros((1, pb), np.int32)
        toks[0, :plen] = req.prompt
        batch = {"tokens": jnp.asarray(toks),
                 **self.adapter.prefill_extras(self.model, 1)}
        last = jnp.asarray([off + plen - 1], jnp.int32)
        self.compiled_prefill_shapes.add(pb)
        # the prompt bucket's EXECUTED flash tiles — resolved by the
        # router (warm buckets: memo hit, zero probes), jitted static
        tiles = self.router.prefill_tiles(off + pb) \
            if self.use_prefill_tiles else None
        with self.obs.span("prefill", rid=req.rid,
                           prompt_len=plen, bucket=pb,
                           tiles=tiles):
            t0 = time.perf_counter()
            logits, rcache = self._prefill(self.params, batch, last,
                                           prefill_tiles=tiles,
                                           pad_to=(off + pb) if off else None)
            with self.obs.span("wait"):
                logits = jax.block_until_ready(logits)
            self.metrics.add_prefill_time(time.perf_counter() - t0)

        with self.obs.span("write_row", rid=req.rid, prompt_len=plen):
            pm = sm = None
            if self.paged:
                blocks = self.pool.lease(req.rid).blocks
                self._tables[req.slot] = self.pool.block_table(req.rid)
                self._tables_dev = None
                pm = self._page_map(blocks, off + plen)
                if self.kv_spec.quantized:
                    sm = self._scale_map(blocks)
            self._cache = self._write_row(self._cache, req.slot, rcache,
                                          off + plen, self.pool.kv_len,
                                          page_map=pm, scale_map=sm,
                                          page_block=self._block_size)
            self._set_in_flight(req.slot, True)
        with self.obs.span("sample", rows=1):
            first = int(jnp.argmax(logits[0, -1]))
            req.generated.append(first)
            self._tokens[req.slot, 0] = first
        t = self._now()
        self.metrics.on_admit(req.rid, now)
        self.metrics.on_first_token(req.rid, t)

    # -- chunked prefill --------------------------------------------------

    def _chunk_size(self, tiles: Optional[tuple]) -> int:
        if isinstance(self._chunk_cfg, int):
            return max(1, self._chunk_cfg)
        # "auto": the tuned tile's block_q — the quantum the tuner
        # already decided a prefill sweep should advance in (32 for
        # attention-free families, which have no tile decision)
        width = int(tiles[0]) if tiles else 32
        if self._experts:
            # a chunk's step lands whole inside one gap of every decoding
            # row, and the dropless layer carries all chunk x top-k pairs
            # of it, held or not (gathered, weighted and scattered back
            # in float32), so its cost grows top-k times as fast per token
            # as a dense layer's.  Capping the pairs keeps every chunk's
            # stall about one size, whatever the prompt's bucket.
            cap = CHUNK_PAIRS // max(self.cfg.moe_topk, 1)
            width = min(width, 1 << (cap.bit_length() - 1))
        return width

    def _admit_chunked(self, req: Request, now: float) -> None:
        """Seat the request (slot + blocks leased, capacity held) but
        run its prefill chunk-by-chunk between decode ticks instead of
        all at once.  The slot's block-table row is NOT published until
        the row lands (``_finish_chunked``): a recycled slot's stale
        ``pos`` would otherwise scatter interim decode writes through
        the new table — harmlessly into private blocks before prefix
        sharing, but into another request's data once the leading
        entries alias radix-shared blocks.  Unpublished (-1) rows drop
        their writes in ``_cache_write``, and decode skips the request
        until ``write_row`` lands the finished row.

        With a radix match pending (``RadixCache.prepare`` ran at
        admission), the matched prefix seeds the private row cache —
        shared full blocks plus the copied boundary tail — and chunked
        prefill RESUMES mid-prompt at the traced start offset, paying
        compute only for the private suffix."""
        if self.adapter.prefill_buckets:
            pb = self.adapter.prefill_len(req.prompt_len,
                                          self.router.quantize_prompt)
        else:
            # exact-length families: the private row cache is
            # length-free, so no bucketing is needed — chunking itself
            # bounds the compile set (one shape per chunk width)
            pb = req.prompt_len
        tiles = self.router.prefill_tiles(pb) if self.use_prefill_tiles \
            else None
        blocks = None
        if self.paged:
            blocks = self.pool.lease(req.rid).blocks
        cache = self.model.init_cache(1, pb,
                                      expand_kv=self.plan.expand_kv)
        # length-bound caches clamp the chunk to the row: exact-mode
        # buckets are the raw prompt length while the auto width (tuned
        # block_q) is padded to a tile multiple, so an unclamped chunk
        # would overrun the cache write.  Length-free row caches (ssm)
        # keep the configured width — their compile key is the width
        # alone, and clamping would leak one compile per short prompt.
        chunk = self._chunk_size(tiles)
        if self.adapter.grows_with_len:
            chunk = min(chunk, pb)
        task = _ChunkTask(req=req, cache=cache,
                          toks=np.asarray(req.prompt, np.int32), pb=pb,
                          tiles=tiles, chunk=chunk, blocks=blocks)
        if self._radix is not None:
            m = self._radix.claim(req.rid)
            if m is not None and m.hit:
                self._radix_seed(task, m)
            self._radix.seeded(req.rid)
        self._chunk_tasks.append(task)
        self._prefilling[req.rid] = task
        self.metrics.on_admit(req.rid, now)

    def _radix_seed(self, task: _ChunkTask, m) -> None:
        """Seed a chunk task's private row cache from its radix match:
        gather the matched positions' k/v out of the pool's physical
        blocks (dequantizing on int8 pools — the boundary tail is
        re-quantized by ``write_row``, the bounded-error COW the int8
        tests budget for), land them at the row's leading positions, and
        move the traced resume offset past them.  The matched FULL
        blocks stay shared (``task.start`` keeps ``write_row`` off
        them); the tail's tokens become private data the moment they
        enter the row cache."""
        bs = self._block_size
        plen = task.req.prompt_len
        resume = m.resume(plen, bs)
        if resume <= 0:
            return
        from repro.kernels.paged_gather import flat_position

        tok = np.arange(resume)
        pid = np.empty(resume, np.int64)
        nfull = len(m.blocks) * bs
        if nfull:
            pid[:nfull] = np.asarray(m.blocks, np.int64)[tok[:nfull] // bs]
        if resume > nfull:
            pid[nfull:] = m.tail_block
        flat = jnp.asarray(
            flat_position(pid, tok, self.slots, self.pool.kv_len, bs),
            jnp.int32)
        cache = dict(task.cache)
        for key in self.adapter.length_keys:
            arr = self._cache[key]                   # (L, B, T, G, hd)
            n, b, t = arr.shape[0], arr.shape[1], arr.shape[2]
            vals = arr.reshape((n, b * t) + arr.shape[3:])[:, flat]
            skey = key + "_scale"
            if skey in self._cache:
                # per-(physical block, kv head) symmetric dequant — the
                # same flat scale identity the fused kernels resolve
                nb = t // bs
                sidx = jnp.asarray(
                    ((pid % self.slots) * nb + pid // self.slots)
                    .astype(np.int32))
                sarr = self._cache[skey]             # (L, B, nb, G)
                scl = sarr.reshape(n, b * nb, -1)[:, sidx]   # (L, r, G)
                vals = vals.astype(jnp.float32) * scl[..., None]
            cache[key] = cache[key].at[:, 0, :resume].set(
                vals.astype(cache[key].dtype))
        cache["pos"] = jnp.int32(resume)
        task.cache = cache
        task.start = m.write_start(bs)
        task.done = resume
        n_hit = resume
        self._radix.stats.hit_tokens += n_hit
        self.obs.instant("radix_hit", rid=task.req.rid, tokens=n_hit,
                         shared_blocks=len(m.blocks), tail=m.tail_len)
        self.obs.count("radix_hit_tokens", n_hit)

    def _prefill_tick(self) -> bool:
        """Advance the oldest in-flight chunked prefill by ONE chunk —
        the interleaving quantum: at most one chunk of prefill work runs
        between consecutive decode ticks, so a long prompt can no longer
        stall the pool for its whole length."""
        if not self._chunk_tasks:
            return False
        task = self._chunk_tasks[0]
        c, start = task.chunk, task.done
        n = min(c, len(task.toks) - start)
        buf = np.zeros((1, c), np.int32)
        buf[0, :n] = task.toks[start:start + n]
        cache_len = task.pb if self.adapter.grows_with_len else 0
        self.compiled_chunk_shapes.add((c, cache_len, task.tiles))
        with self.obs.span("prefill_chunk", rid=task.req.rid,
                           bucket=task.pb, chunk=c, start=start,
                           tiles=task.tiles) as sp:
            t0 = time.perf_counter()
            out = self._chunk_step(
                self.params, task.cache, jnp.asarray(buf), jnp.int32(n),
                prefill_tiles=task.tiles)
            logits, task.cache = out[:2]
            load = self._start_load_readback(out)
            with self.obs.span("wait"):
                logits = jax.block_until_ready(logits)
            self.metrics.add_prefill_time(time.perf_counter() - t0)
            if load is not None:
                sp.set(**_expert_load_attrs(load))
        task.done += n
        if task.done >= len(task.toks):
            self._finish_chunked(task, logits, n)
        return True

    def _finish_chunked(self, task: _ChunkTask, logits, n: int) -> None:
        req = task.req
        with self.obs.span("write_row", rid=req.rid,
                           prompt_len=req.prompt_len):
            pm = sm = None
            if self.paged:
                # publish the slot's table row only now — see
                # _admit_chunked
                self._tables[req.slot] = self.pool.block_table(req.rid)
                self._tables_dev = None
                pm = self._page_map(task.blocks, req.prompt_len,
                                    start=task.start)
                if self.kv_spec.quantized:
                    sm = self._scale_map(task.blocks)
                # decode appends land in the prompt's boundary block
                # onward; sharing discipline requires that block be
                # PRIVATE (shared blocks are read-only by contract)
                assert self.pool.refcount(
                    task.blocks[req.prompt_len // self._block_size]) == 1, \
                    "decode-append block is shared"
            self._cache = self._write_row(self._cache, req.slot,
                                          task.cache, req.prompt_len,
                                          self.pool.kv_len, page_map=pm,
                                          scale_map=sm,
                                          page_block=self._block_size,
                                          start=task.start)
            self._set_in_flight(req.slot, True)
        if self._radix is not None:
            # index the request's fully-written prompt blocks (shared
            # prefix nodes are reused; only new nodes retain); the
            # partial tail joins at retirement, once decode stops
            # appending into it
            self._radix.insert(req.prompt, task.blocks)
        with self.obs.span("sample", rows=1):
            first = int(jnp.argmax(logits[0, n - 1]))
            req.generated.append(first)
            self._tokens[req.slot, 0] = first
        self.metrics.on_first_token(req.rid, self._now())
        self.obs.instant("prefill_complete", rid=req.rid,
                         prompt_len=req.prompt_len, chunk=task.chunk,
                         chunks=-(-len(task.toks) // task.chunk))
        self._chunk_tasks.pop(0)
        del self._prefilling[req.rid]

    # -- decode -----------------------------------------------------------

    def _set_in_flight(self, slot: int, on: bool) -> None:
        if self._in_flight[slot] != on:
            self._in_flight[slot] = on
            self._in_flight_dev = None

    def _start_load_readback(self, out):
        """A step's expert load, its copy to the host started, while
        tracing (the span attributes read it after the step's wait);
        None for dense steps or with tracing off."""
        if len(out) < 3 or not self.obs.enabled:
            return None
        out[2].copy_to_host_async()
        return out[2]

    def _decode_tick(self) -> None:
        self.compiled_decode_shapes.add(self._decode_shape())
        # the bucket's resolved plan, whose decode_block parameterizes
        # the step about to run (None for attention-free families)
        plan = self._current_plan()
        kw = {}
        if self.paged and self.adapter.grows_with_len:
            # live block tables are DATA (they change at admit/retire,
            # so the device upload is memoized, not per-tick); the block
            # size is the static layout constant
            if self._tables_dev is None:
                self._tables_dev = jnp.asarray(self._tables)
            kw = dict(page_tables=self._tables_dev,
                      page_block=self._block_size,
                      # the router's tuned fused block_s — None drops the
                      # read back to gather-then-sweep (the ablation)
                      paged_decode_block=(plan.paged_decode_block
                                          if self.fused_decode else None))
        if self._experts:
            if self._in_flight_dev is None:
                self._in_flight_dev = jnp.asarray(self._in_flight)
            kw["rows"] = self._in_flight_dev
        # the summed context of the rows this tick decodes (each reads
        # its prompt and every token it has generated), and on the paged
        # pool the pages that covers: what the fused read fetches a layer
        ctx = pages = None
        if self.obs.enabled:
            lens = [r.prompt_len + len(r.generated)
                    for r in self.scheduler.live
                    if not r.done and r.rid not in self._prefilling]
            ctx = sum(lens)
            if kw:
                pages = sum(-(-n // self._block_size) for n in lens)
        # the span records the EXECUTED mapping: the fused block_s when
        # the paged read runs fused, the dense decode_block otherwise
        with self.obs.span("decode_tick", bucket=self.pool.kv_len,
                           decode_block=plan.decode_block,
                           paged_decode_block=kw.get("paged_decode_block"),
                           live=len(self.scheduler.live), slots=self.slots,
                           ctx_tokens=ctx, pages=pages,
                           pool_len=self.pool.kv_len) as sp:
            t0 = time.perf_counter()
            out = self._decode(self.params, dict(self._cache),
                               jnp.asarray(self._tokens),
                               decode_block=plan.decode_block, **kw)
            logits, self._cache = out[:2]
            load = self._start_load_readback(out)
            with self.obs.span("wait"):
                logits = jax.block_until_ready(logits)
            dt = time.perf_counter() - t0
            self.metrics.add_decode_time(dt)
            if load is not None:
                sp.set(**_expert_load_attrs(load))
        if self.retune is not None:
            # the tick's EXECUTED mapping (mirrors the span attribution):
            # the fused block_s when the paged read ran fused, the dense
            # decode_block otherwise, nothing for attention-free families
            pdb = kw.get("paged_decode_block")
            kernel, value = (("paged_decode", pdb) if pdb is not None
                             else ("decode_attention", plan.decode_block)
                             if plan.decode_block is not None
                             else (None, None))
            self.retune.observe_tick(self.pool.kv_len, kernel, value, dt)
        with self.obs.span("sample") as sp:
            lg = logits[:, 0] if logits.ndim == 3 else logits
            nxt = np.asarray(jnp.argmax(lg, axis=-1), np.int32)
            live = self.scheduler.live_by_slot()
            n_dec = 0
            for slot, req in live.items():
                # rows still chunk-prefilling ride the step (their leased
                # row is overwritten by write_row at completion) but
                # their outputs are not real tokens yet
                if not req.done and req.rid not in self._prefilling:
                    req.generated.append(int(nxt[slot]))
                    self._tokens[slot, 0] = int(nxt[slot])
                    n_dec += 1
            self.metrics.on_step(self._now(), n_dec, self.slots)
            sp.set(rows=n_dec)

    # -- main loop --------------------------------------------------------

    def _retire_finished(self, on_complete) -> None:
        with self.obs.span("retire"):
            now = self._now()
            for req in self.scheduler.live:
                eos = self.eos_id is not None and req.generated \
                    and req.generated[-1] == self.eos_id
                if not (req.done or eos):
                    continue
                slot = req.slot
                if self._radix is not None and \
                        req.rid not in self._prefilling:
                    # the partial prompt-tail block becomes indexable
                    # only now — its owner stops appending decode tokens
                    self._radix.insert_tail(
                        req.prompt, self.pool.lease(req.rid).blocks)
                self.scheduler.finish(req)
                if self.paged and slot is not None:
                    self._tables[slot] = -1      # unmap: blocks recycle
                    self._tables_dev = None
                if slot is not None:
                    self._set_in_flight(slot, False)
                self.obs.instant("slot_recycle", rid=req.rid, slot=slot,
                                 generated=len(req.generated))
                self.outputs[req.rid] = list(req.prompt) + list(req.generated)
                self.metrics.on_done(req.rid, now, len(req.generated))
                if on_complete is not None:
                    on_complete(req, now)

    def _admit_ready(self) -> None:
        with self.obs.span("admit"):
            now = self._now()
            self.scheduler.poll(now)
            need = self.scheduler.peek_need_len()
            if need is not None:
                target = self.spec.quantize(need)
                if target > self.pool.kv_len:
                    self._grow_pool(target)
            for req in self.scheduler.admissible():
                # resolve the bucket's tuned kernel plans BEFORE the
                # request joins the pool — the runtime mapping decision
                # of the paper, warm buckets answered by the tuning cache
                # with zero probes
                self._current_plan()
                self._admit(req, now)

    def _iterate(self, on_complete) -> bool:
        """One iteration of the engine loop; False when the loop must
        stop with requests still queued (nothing left to run)."""
        self._admit_ready()
        # one prefill chunk per loop iteration, interleaved with the
        # decode tick below — long prompts advance without ever
        # stalling the decoding pool for their whole length
        stepped = self._prefill_tick()
        decodable = any(r.rid not in self._prefilling
                        for r in self.scheduler.live)
        if decodable:
            self._decode_tick()
            self._retire_finished(on_complete)
        elif not stepped:
            nxt = self.scheduler.next_arrival
            if nxt is not None:
                self._fast_forward(nxt)    # idle: jump to next arrival
            elif self.scheduler.backlog:
                # queue head can never be seated (block budget): shed
                # it rather than livelock — admission control's floor
                self.scheduler.shed_head()
            else:
                return False
        if self.retune is not None and self.retune.poll():
            # the router's table changed under us (trial start or
            # revert): drop the plan memo so the next tick re-reads it
            self._plan_len = -1
        return True

    def _report(self) -> ServeReport:
        with self.obs.span("report"):
            return self.report()

    def run(self, *, on_complete=None,
            max_steps: Optional[int] = None) -> ServeReport:
        """Drain the queue; returns the run's ``ServeReport``.  Each
        loop iteration is one ``step`` span; the last one ends with the
        report."""
        steps = 0
        while not self.scheduler.idle:
            with self.obs.span("step"):
                more = self._iterate(on_complete)
                steps += 1
                if (not more or self.scheduler.idle
                        or (max_steps is not None and steps >= max_steps)):
                    return self._report()
        return self._report()

    def report(self) -> ServeReport:
        """Snapshot the run's ``ServeReport`` (also returned by
        ``run``); callable any time, including mid-run."""
        s = self.metrics.summary()
        if self.verbose:
            print(f"[serve] {self.cfg.name}: {s.n_completed}/{s.n_requests} "
                  f"done, {s.output_tokens} tok @ {s.tokens_per_s:.1f} tok/s, "
                  f"ttft p50 {s.ttft_p50_s * 1e3:.1f}ms, util "
                  f"{s.utilization:.2f}")
        return ServeReport(
            summary=s,
            outputs=dict(self.outputs),
            completed=list(self.scheduler.completed),
            rejected=list(self.scheduler.rejected),
            router_stats=dataclasses.asdict(self.router.stats),
            compiled_decode_shapes=len(self.compiled_decode_shapes),
            compiled_prefill_shapes=len(self.compiled_prefill_shapes),
            compiled_chunk_shapes=len(self.compiled_chunk_shapes),
            pool_growths=self.pool_growths,
            retune=(None if self.retune is None else {
                "stats": dataclasses.asdict(self.retune.stats),
                "decisions": [dataclasses.asdict(d)
                              for d in self.retune.decisions],
            }),
            radix=(self._radix.as_report()
                   if self._radix is not None else None),
        )
