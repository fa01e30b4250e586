"""Serving driver — a thin CLI over the ``repro.serve`` engine.

The real serving loop lives in ``repro.serve.engine`` (continuous
batching, bucketed tuned dispatch, family-generic CacheAdapter pool,
paged-KV accounting; see docs/SERVING.md).  This module keeps two entry
points:

  * ``serve_batch`` — the fixed-mix convenience API (all requests
    submitted at once, slots = requests): what the system tests and
    quickstart examples call.  Every adapter-backed family — dense, MoE,
    SSM, hybrid, encoder-decoder — runs on the engine's ragged pool;
    there is no fixed-batch fallback loop anymore;
  * ``main`` — synthetic-traffic CLI: Poisson arrivals through the
    engine, with the tuner's ``--measure {off,cached,live}`` passthrough
    so the profiler's measured-cost tuning can refine serving buckets
    (``cached`` replays recorded traces and is the safe default — no
    device work on a cache miss, clean fallback on an empty store).

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m \\
      --requests 16 --rate 8 --measure cached \\
      --trace serve-trace.json --metrics-json serve-metrics.json
"""

from __future__ import annotations

import argparse
import dataclasses
import json

import numpy as np

from repro.configs import get_config
from repro.core.mapper import MappingPolicy
from repro.launch.compile_cache import enable_compile_cache
from repro.serve import BucketSpec, ServeEngine, TrafficConfig, drive
from repro.tuner import MEASURE_MODES


@dataclasses.dataclass
class ServeStats:
    """Back-compat summary of one fixed-mix ``serve_batch`` run."""

    n_requests: int
    prefill_tokens: int
    decoded_tokens: int
    prefill_s: float
    decode_s: float
    outputs: list


def serve_batch(arch: str, prompts: list[list[int]], *,
                max_new_tokens: int = 16, reduced: bool = True,
                mesh=None, params=None, verbose: bool = True,
                policy: MappingPolicy | str = MappingPolicy.TUNED,
                measure: str = "off") -> ServeStats:
    """Serve a fixed request mix: every prompt admitted at t=0, one slot
    each, greedy decode to ``max_new_tokens``, on the engine's ragged
    pool (per-row positions: no request reads another's padding).  The
    family's ``CacheAdapter`` supplies the pool state, so this is one
    code path for all served families."""
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    max_len = max(len(p) for p in prompts) + max_new_tokens + 1
    # the engine's paged-KV pool (on by default) needs whole-block rows
    max_len = -(-max_len // 16) * 16
    engine = ServeEngine(cfg, slots=len(prompts), max_len=max_len,
                         mesh=mesh, params=params, policy=policy,
                         measure=measure, verbose=False)
    reqs = [engine.submit(p, max_new_tokens=max_new_tokens)
            for p in prompts]
    report = engine.run()
    s = report.summary
    stats = ServeStats(
        n_requests=len(prompts),
        prefill_tokens=sum(len(p) for p in prompts),
        decoded_tokens=s.output_tokens,
        prefill_s=s.prefill_s, decode_s=s.decode_s,
        outputs=[report.outputs[r.rid] for r in reqs])
    if verbose:
        print(f"[serve] {cfg.name}: {stats.n_requests} reqs, prefill "
              f"{stats.prefill_tokens} tok in {stats.prefill_s:.2f}s, decoded "
              f"{stats.decoded_tokens} tok in {stats.decode_s:.2f}s "
              f"({stats.decoded_tokens / max(stats.decode_s, 1e-9):.1f} "
              f"tok/s)")
    return stats


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=8.0,
                    help="open-loop Poisson arrivals per second")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--mode", choices=("open", "closed"), default="open")
    ap.add_argument("--no-paged", action="store_true",
                    help="disable physical KV paging (fused table-consuming "
                         "decode) and serve from contiguous cache rows; "
                         "required for --bucket-mode exact")
    ap.add_argument("--bucket-mode",
                    choices=("pow2", "linear", "exact", "fixed"),
                    default="pow2")
    ap.add_argument("--policy", default="tuned",
                    choices=[p.value for p in MappingPolicy])
    ap.add_argument("--measure", choices=MEASURE_MODES, default="cached",
                    help="tuner refinement on bucket misses: cached replays "
                         "recorded profiler traces (safe default), live "
                         "measures on-device, off is analytic-only")
    ap.add_argument("--retune", choices=("off", "inline", "background"),
                    default="off",
                    help="live in-flight retuning: drift-flagged buckets "
                         "are re-resolved over the serving-fed trace store "
                         "and A/B-trialled on real decode ticks — a slower "
                         "candidate is never adopted.  'inline' re-resolves "
                         "between ticks (deterministic); 'background' moves "
                         "the re-resolve to a worker thread")
    ap.add_argument("--prefill-chunk", metavar="N|auto|none",
                    default="auto",
                    help="prefill prompts in N-token chunks interleaved "
                         "with decode ticks instead of all at once — long "
                         "prompts stop stalling the pool.  'auto' (the "
                         "default) uses the bucket's tuned flash tile "
                         "(block_q) as the chunk; 'none' opts out to "
                         "whole-prompt prefill")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix prefix sharing: requests whose prompts "
                         "share a leading token run alias the SAME "
                         "physical KV blocks (refcounted) and resume "
                         "prefill mid-prompt — system-prompt traffic "
                         "stops recomputing its preamble.  Engages on "
                         "paged + chunked-prefill attention families "
                         "(dense/moe); a no-op elsewhere")
    ap.add_argument("--shared-prefix", type=int, metavar="N", default=0,
                    help="give 90%% of synthesized requests a common "
                         "N-token preamble (the traffic shape "
                         "--prefix-cache exists for; 0 = independent "
                         "prompts)")
    ap.add_argument("--kv-dtype", choices=("fp32", "int8"), default="fp32",
                    help="KV pool storage dtype: int8 stores symmetric "
                         "per-(block, head) codes + scales (~1/4 of the "
                         "fp32 pool bytes) with dequantization fused into "
                         "the tuned decode sweep; requires the paged pool")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="write the run's obs trace here (.json -> "
                         "Perfetto/Chrome form, else versioned JSONL; "
                         "inspect with tools/trace_view.py)")
    ap.add_argument("--metrics-json", metavar="PATH", default=None,
                    help="dump the ServeReport summary as JSON")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    vocab = (cfg if args.full else cfg.reduced()).vocab_size
    paged = not args.no_paged
    if paged:
        # paged pools need whole-block lattice lengths (block_size=16)
        args.max_len = -(-args.max_len // 16) * 16
    rng = np.random.default_rng(args.seed)
    lo, hi = 4, max(8, args.max_len - args.max_new - 1)
    traffic = TrafficConfig(
        n_requests=args.requests, rate=args.rate, mode=args.mode,
        prompt_dist=("uniform", lo, min(hi, 48)),
        output_dist=("uniform", 2, args.max_new),
        concurrency=args.slots, vocab=vocab,
        seed=int(rng.integers(1 << 30)),
        shared_prefix=((args.shared_prefix, 0.9)
                       if args.shared_prefix else None))
    tracer = None
    if args.trace:
        from repro.obs import Tracer
        tracer = Tracer()
    chunk = args.prefill_chunk
    if chunk == "none":
        chunk = None
    elif chunk is not None and chunk != "auto":
        chunk = int(chunk)
    engine = ServeEngine(
        args.arch, slots=args.slots, max_len=args.max_len,
        reduced=not args.full, paged=paged,
        spec=BucketSpec(max_len=args.max_len, mode=args.bucket_mode),
        policy=args.policy, measure=args.measure, tracer=tracer,
        retune=args.retune, prefill_chunk=chunk,
        kv_dtype=args.kv_dtype, prefix_cache=args.prefix_cache,
        verbose=True)
    report = drive(engine, traffic)
    s = report.summary
    print(f"[serve] ttft p50/p95 {s.ttft_p50_s * 1e3:.1f}/"
          f"{s.ttft_p95_s * 1e3:.1f} ms, tpot p50 {s.tpot_p50_s * 1e3:.2f} ms, "
          f"{s.tokens_per_s:.1f} tok/s, util {s.utilization:.2f}, "
          f"compiles decode={report.compiled_decode_shapes} "
          f"prefill={report.compiled_prefill_shapes}, "
          f"router={report.router_stats}")
    if report.retune is not None:
        st = report.retune["stats"]
        print(f"[serve] retune: scans={st['scans']} trials={st['trials']} "
              f"adopted={st['adopted']} rejected={st['rejected']}")
    if report.radix is not None:
        rx = report.radix
        print(f"[serve] radix: hit rate {rx['hit_rate']:.2f} "
              f"({rx['hits']}/{rx['lookups']}), "
              f"{rx['hit_tokens']} prompt tokens reused, "
              f"{rx['evicted_blocks']} blocks evicted")
    if tracer is not None:
        from repro.obs import write_trace
        path = write_trace(tracer, args.trace)
        print(f"[serve] trace ({len(tracer.spans())} spans) -> {path}")
    if args.metrics_json:
        payload = {
            "summary": s.as_dict(),
            "router_stats": report.router_stats,
            "compiled_decode_shapes": report.compiled_decode_shapes,
            "compiled_prefill_shapes": report.compiled_prefill_shapes,
            "compiled_chunk_shapes": report.compiled_chunk_shapes,
            "pool_growths": report.pool_growths,
            "n_rejected": len(report.rejected),
            "retune": report.retune,
            "radix": report.radix,
        }
        with open(args.metrics_json, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
        print(f"[serve] metrics -> {args.metrics_json}")


if __name__ == "__main__":
    main()
