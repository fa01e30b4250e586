#!/usr/bin/env python3
"""Find a cell's knee: serve its open-loop mix at several fixed rates in
one process (one engine, one set of weights) and print, per rate, the
TTFT and gap tails, tokens/s and the share of requests that met both
limits.  The benchmark's own runs never call this.

    python3 bench/tools/sweep.py --workload smollm-135m.chat \
        --rates 0.8,1.2,1.6 --seconds 120 --ttft-ms 2000 --itl-ms 400

``--dump-trace DIR`` also records one window with the profiler and
writes the trace's planes, lines and heaviest operations to
``DIR/trace_summary.json`` (for naming the kernels the readers match).
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
import run as bench_run  # noqa: E402


def ttfts(win) -> list:
    """Due to first token, every request due in the window; one still
    unanswered at the close counts at the close (a wait it had at
    least)."""
    return [(r.stamps[0] if r.stamps else win.closed) - r.due
            for r in win.recs]


def attainment(win, ttft_s: float, itl_s: float) -> float:
    """Share of the requests due whose first token came within
    ``ttft_s`` and whose gaps up to the close stayed within ``itl_s``."""
    ok = 0
    for r, t in zip(win.recs, ttfts(win)):
        st = [x for x in r.stamps if x <= win.closed]
        gaps = [b - a for a, b in zip(st, st[1:])]
        if not r.rejected and t <= ttft_s and (not gaps or
                                               max(gaps) <= itl_s):
            ok += 1
    return ok / max(1, len(win.recs))


def dump_trace(prof_dir: str, out_dir: Path) -> None:
    from jax.profiler import ProfileData

    from harness import trace

    path = trace.find_xplane(prof_dir)
    pd = ProfileData.from_file(path)
    planes = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            tot: dict = {}
            for ev in evs:
                tot[ev.name] = tot.get(ev.name, 0) + ev.duration_ns
            top = sorted(tot.items(), key=lambda kv: -kv[1])[:40]
            sample = {}
            for ev in evs:
                if ev.name in dict(top[:15]) and ev.name not in sample:
                    sample[ev.name] = _stats(ev)
            lines.append({"line": line.name, "events": len(evs),
                          "top_ns": top, "stats": sample})
        planes.append({"plane": plane.name, "lines": lines})
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / "trace_summary.json").write_text(json.dumps(planes, indent=1))
    ex = trace.extract(path)
    (out_dir / "trace_extract.json").write_text(json.dumps(ex))


def _stats(ev):
    try:
        return [[str(k), str(v)[:300]] for k, v in ev.stats]
    except (TypeError, ValueError) as e:
        return [["error", str(e)]]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=120,
                    help="window per rate; its requests are not drained")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--ttft-ms", type=float, required=True)
    ap.add_argument("--itl-ms", type=float, required=True)
    ap.add_argument("--dump-trace", default=None)
    args = ap.parse_args()
    sys.path.insert(0, str(bench_run.ROOT / "src"))
    from harness import driver, spec
    from harness.stats import percentile, token_gaps, tokens_in_window

    cell = spec.load_cell(args.workload, bench_run.ROOT)
    jax = bench_run.setup_jax(False)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2
    sizes = spec.sizes(cell.config)
    mix_mod = spec.load_module(
        spec.traffic_module_path(cell.traffic["generator"], bench_run.ROOT))
    counter, clock = driver.CompileCounter(), driver.WindowClock()
    _, _, engine = bench_run.build(cell, args.seed, False, False, clock)
    drv = driver.Driver(engine, clock, counter,
                        host_spans=bool(args.dump_trace))
    for k, rate in enumerate(float(x) for x in args.rates.split(",")):
        mix = dict(cell.traffic, rate_per_s=rate)
        seconds = args.seconds
        sched = mix_mod.schedule(mix, args.seed + k, seconds,
                                 sizes["vocab_size"])
        w = drv.warm(sched, sizes["vocab_size"])
        prof = None
        if args.dump_trace and k == 0:
            prof = tempfile.mkdtemp(prefix="sweep-trace-")
        win = drv.window(sched, seconds, profile_s=4.0 if prof else 0.0,
                         profile_dir=prof, drain=False)
        if prof:
            dump_trace(prof, Path(args.dump_trace))
        st = [r.stamps for r in win.recs]
        ttft = ttfts(win)
        gaps = token_gaps(st, win.end)
        row = {
            "rate": rate, "attempted": len(win.recs),
            "unanswered": sum(1 for r in win.recs if not r.stamps),
            "rejected": sum(1 for r in win.recs if r.rejected),
            "ttft_p50_ms": 1e3 * percentile(ttft, 50),
            "ttft_p95_ms": 1e3 * percentile(ttft, 95),
            "itl_p50_ms": 1e3 * percentile(gaps, 50),
            "itl_p95_ms": 1e3 * percentile(gaps, 95),
            "seconds": seconds,
            "tokens_per_s": tokens_in_window(st, win.origin, win.end)
            / seconds,
            "offered_tokens_per_s": rate * sum(r.max_new for r in
                                               sched.requests)
            / len(sched.requests),
            "attainment": attainment(win, args.ttft_ms / 1e3,
                                     args.itl_ms / 1e3),
            "unfinished_at_close": len(drv.active),
            "compiles_in_window": win.compiles,
            "warm_s": w["warm_s"],
        }
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
