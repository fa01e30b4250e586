#!/usr/bin/env python3
"""Readings that a cell's ``correct`` limit is set from, for many seeds
in one process: for each seed, fresh weights, the cell's traffic served
through the program for a short window at the cell's own load and
drained, then on the same seeded sample of served requests

* the program's reading: the widest gap by which a served token's
  reference logit lies below the reference's best (what ``run.py``
  compares), and
* the control's reading: the same gap for the token that the reference
  computed in float8 (the family's ``logits``, ``quant="fp8"``) puts
  first.

    python3 bench/tools/control.py --workload smollm-135m.chat \
        --seeds 1,2,3 --seconds 10

The benchmark's own runs never call this.  ``--rehearse`` runs it on
the CPU at tiny widths.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
import run as bench_run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    sys.path.insert(0, str(bench_run.ROOT / "src"))
    from harness import check, driver, spec, weights
    from repro.models import build_model

    cell = spec.load_cell(args.workload, bench_run.ROOT)
    jax = bench_run.setup_jax(args.rehearse)
    if not args.rehearse and jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2
    sizes = spec.sizes(cell.config, args.rehearse)
    serve = spec.serve_settings(cell.config, args.rehearse)
    mix_mod = spec.load_module(
        spec.traffic_module_path(cell.traffic["generator"], bench_run.ROOT))
    mix = cell.traffic
    if args.rehearse:
        mix = mix_mod.scaled(mix, serve["max_len"] /
                             cell.config["serve"]["max_len"])
    seeds = [int(s) for s in args.seeds.split(",")]
    counter, clock = driver.CompileCounter(), driver.WindowClock()
    cfg, _, engine = bench_run.build(cell, seeds[0], args.rehearse, False,
                                     clock)
    init = build_model(cfg).init
    drv = driver.Driver(engine, clock, counter)
    for k, seed in enumerate(seeds):
        params = engine.params if k == 0 else weights.make_params(
            init, seed, cell.family.fan_in)
        engine.params = params
        sched = mix_mod.schedule(mix, seed, args.seconds, sizes["vocab_size"])
        if k == 0:
            drv.warm(sched, sizes["vocab_size"])
        engine.reset()
        win = drv.window(sched, args.seconds)
        done = [check.Served(list(r.req.prompt), list(r.req.generated))
                for r in win.recs if r.done]
        items = check.sample(done, seed, bench_run.SAMPLE_TOKENS,
                             bench_run.SAMPLE_REQUESTS)
        prog = check.served_gaps(cell.family, params, sizes, items)
        ctrl = check.control_gaps(cell.family, params, sizes, items)
        print(json.dumps({
            "seed": seed, "requests": len(items), "tokens": int(prog.size),
            "attempted": len(win.recs),
            "failed": sum(1 for r in win.recs if r.rejected or not r.done),
            "program_gap": float(prog.max()),
            "program_gap_p99": float(sorted(prog)[int(0.99 * (len(prog) - 1))]),
            "control_gap": float(ctrl.max()),
            "control_flips": int((ctrl > 0).sum()),
            "program_flips": int((prog > 0).sum())}), flush=True)
        del params
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
