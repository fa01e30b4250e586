#!/usr/bin/env python3
"""The chip benchmark: one cell of ``BENCHMARK.json``, one run.

    python3 bench/run.py --workload smollm-135m.chat --seed 7 \
        --seconds 30 --trace 0

From the root of a checkout.  Makes the weights on the device from the
seed, builds the program's serving engine for the cell's configuration,
warms every shape the cell's traffic will use, serves the traffic on
the wall clock for ``--seconds``, drains the requests due in that
window, then checks a seeded sample of what was served against the
plain reference of the configuration's family (``bench/families/``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics
with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``:
each number compared beside its limit.  The same checks end standard
error.  No TPU, or fewer chips than the cell asks for: exit 2, no
result.

``--rehearse`` runs the same path on the CPU at tiny widths, for
finding wrong paths without a chip.  It prints counts and the check,
never a device metric.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
#: the persistent compilation cache: a fixed directory in the checkout
CACHE_DIR = ROOT / ".bench_cache" / "jax"
#: requests in the correctness sample: at least this many served tokens,
#: at most this many requests (the longest request is always in it)
SAMPLE_TOKENS = 256
SAMPLE_REQUESTS = 8
#: the profiler records the window's last seconds (--trace 1)
PROFILE_S = 4.0


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, tiny widths, counts only (no device metric)")
    return ap.parse_args(argv)


def setup_jax(rehearse: bool):
    """Environment before JAX starts: the compile cache in the checkout
    (set here, whatever the machine set), and the CPU for a rehearsal."""
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax


def build(cell, seed: int, rehearse: bool, trace: bool, clock):
    """The weights and the program's engine for the cell."""
    import jax

    from harness import spec, weights
    from repro.models import build_model
    from repro.obs.trace import Tracer
    from repro.serve import ServeEngine
    from repro.tuner import TuningCache

    cfg = spec.model_config(cell.config, rehearse)
    serve = spec.serve_settings(cell.config, rehearse)
    params = weights.make_params(build_model(cfg).init, seed,
                                 cell.family.fan_in)
    jax.block_until_ready(params)
    pool = {"bfloat16": "fp32", "int8": "int8"}[serve["pool_dtype"]]
    engine = ServeEngine(cfg, slots=serve["slots"], max_len=serve["max_len"],
                         block_size=serve["block_size"], params=params,
                         policy="tuned", tuning_cache=TuningCache(path=None),
                         kv_dtype=pool, prefill_chunk="auto", clock=clock,
                         tracer=Tracer() if trace else None)
    return cfg, params, engine


def run(args) -> int:
    sys.path.insert(0, str(BENCH_DIR))
    sys.path.insert(0, str(ROOT / "src"))
    from harness import spec

    cell = spec.load_cell(args.workload, ROOT)
    jax = setup_jax(args.rehearse)
    devs = jax.devices()
    dev = devs[0]
    peaks = None
    if not args.rehearse:
        if dev.platform != "tpu" or len(devs) < cell.chips:
            log(f"needs {cell.chips} TPU chip(s); JAX found "
                f"{len(devs)} {dev.platform!r} device(s)")
            return 2
        from harness.peaks import peaks_for

        peaks = peaks_for(dev.device_kind)

    from harness import check, driver, trace
    from harness.view import RunView

    sizes = spec.sizes(cell.config, args.rehearse)
    serve = spec.serve_settings(cell.config, args.rehearse)
    mix_mod = spec.load_module(
        spec.traffic_module_path(cell.traffic["generator"], ROOT))
    mix = cell.traffic
    if args.rehearse:
        mix = mix_mod.scaled(mix, serve["max_len"] /
                             cell.config["serve"]["max_len"])
    counter = driver.CompileCounter()
    clock = driver.WindowClock()
    cfg, params, engine = build(cell, args.seed, args.rehearse,
                                bool(args.trace), clock)
    log(f"weights and engine at {time.perf_counter() - T_START:.3f} s: "
        f"{counter.describe()}")
    sched = mix_mod.schedule(mix, args.seed, args.seconds,
                             sizes["vocab_size"])
    drv = driver.Driver(engine, clock, counter, host_spans=bool(args.trace))
    warm = drv.warm(sched, sizes["vocab_size"])
    if engine.obs.enabled:
        engine.obs.clear()
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s:.3f} s (warm-up {warm['warm_s']:.3f} s over "
        f"{warm['prompt_lengths']} prompt lengths; pool levels "
        f"{warm['chain']})")

    prof_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    win = drv.window(sched, args.seconds,
                     profile_s=min(PROFILE_S, args.seconds / 2)
                     if args.trace else 0.0, profile_dir=prof_dir)
    mem = None
    if not args.rehearse:
        mem = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
    attempted = len(win.recs)
    failed = sum(1 for r in win.recs if r.rejected or not r.done)
    log(f"window {args.seconds:g} s: {attempted} requests due, {failed} "
        f"failed, {win.steps} engine steps, drain {win.drained_s:.3f} s")
    log(f"programs compiled or loaded inside the window: {win.compiles[0]} "
        f"({win.compiles[1]} compiled)")
    if win.lateness:
        lt = sorted(win.lateness)
        log(f"generator lateness: p50 {1e3 * lt[len(lt) // 2]:.3f} ms, max "
            f"{1e3 * lt[-1]:.3f} ms")
    report_share = win.report_s * win.steps / max(win.run_s, 1e-9)
    log(f"engine.run() host share: report rebuild <= {100 * report_share:.2f}% "
        f"of time in run() ({1e3 * win.report_s:.3f} ms per call at the "
        f"close, {win.steps} calls)")

    ex = None
    if args.trace:
        ex = trace.extract(trace.find_xplane(prof_dir))
        shutil.rmtree(prof_dir, ignore_errors=True)
    view = RunView(cell_name=cell.name, seconds=args.seconds,
                   shapes=cell.family.shapes(sizes),
                   slots=serve["slots"], peaks=peaks,
                   window=win, setup_s=setup_s,
                   engine_metrics=engine.metrics,
                   spans=engine.obs.spans() if args.trace else [], trace=ex)
    ttfts = sorted(view.ttfts_s())
    if ttfts:
        log(f"ttft median {1e3 * ttfts[len(ttfts) // 2]:.3f} ms over "
            f"{len(ttfts)} requests")
    entries = cell.per_layer if args.trace else cell.end_to_end
    metrics = {}
    for m in entries:
        reader = spec.load_module(spec.metric_module_path(m["name"], ROOT))
        value = reader.read(view)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    # correctness: free the engine's state, then the reference
    done = [check.Served(list(r.req.prompt), list(r.req.generated))
            for r in win.recs if r.done]
    engine_steps = engine.metrics.steps
    del engine, drv, view
    gc.collect()
    items = check.sample(done, args.seed, SAMPLE_TOKENS, SAMPLE_REQUESTS)
    t_ref, c_ref = time.perf_counter(), counter.mark()
    gaps = check.served_gaps(cell.family, params, sizes, items)
    log(f"reference check: {time.perf_counter() - t_ref:.3f} s, "
        f"{counter.describe(c_ref)}")
    limits = cell.limits["rehearsal"] if args.rehearse else cell.limits
    limit = float(limits["max_logit_gap"]["limit"])
    gap = float(gaps.max()) if gaps.size else float("inf")
    correct = bool(gaps.size) and gap <= limit
    checks = {"max_logit_gap": {"value": gap, "limit": limit,
                                "tokens": int(gaps.size),
                                "requests": len(items)}}

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    out = {"correct": correct, "attempted": attempted, "failed": failed}
    if args.rehearse:
        out.update(rehearsal=True, decode_ticks=len(engine_steps),
                   compiles_in_window=win.compiles[0])
    else:
        out["metrics"] = metrics
        out["device"] = device
    if args.trace and ex is not None and not args.rehearse:
        lo, hi = trace.window_of(ex)
        device["busy_s"] = trace.busy_s(ex)
        device["window_s"] = (hi - lo) / 1e9
        ops = sorted(trace.op_seconds(ex).items(), key=lambda kv: -kv[1])
        gapsd = sorted(trace.idle_gaps(ex).items(), key=lambda kv: -kv[1])
        out["breakdown"] = {"device_ops": [[k, v] for k, v in ops[:10]],
                            "idle_gaps": [[k, v] for k, v in gapsd[:10]]}
    out["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r}; "
            f"{c['tokens']} served tokens of {c['requests']} requests)")
    print(json.dumps(out), flush=True)
    return 0


def main(argv=None) -> int:
    return run(parse(sys.argv[1:] if argv is None else argv))


if __name__ == "__main__":
    sys.exit(main())
