"""Benchmark harness entry point — one section per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run                # full suite
  PYTHONPATH=src python -m benchmarks.run tuner prof     # just these

Sections:
  fig1   execution-trace regimes (paper Fig. 1)
  fig2   450-config mapping-policy sweep (paper Fig. 2 + headline claims)
  kern   Pallas kernel suite under the 4 policies (``name,us_per_call,derived``)
  tuner  tuning-cache dispatch: warm overhead vs cold refine + policy sweep
  prof   profiler: hybrid measured tuning + calibration from the trace fixture
  serve  serving engine: bucketed tuned dispatch vs naive/static (steady state)
  obs    observability: traced vs plain serving + feedback/drift round trip
  retune live retuning: poisoned-plan recovery via A/B-guarded hot swap
  roof   roofline table from the dry-run records (single + multi mesh)

Besides the streamed ``name,us_per_call,derived`` rows, the harness
consolidates every section's CSV rows and returned summary scalars into
one machine-readable ``BENCH_results.json`` (override the path with
``REPRO_BENCH_JSON``; CI uploads it as an artifact so runs are diffable
without scraping logs).
"""

from __future__ import annotations

import contextlib
import io
import json
import os
import sys


def _banner(text: str) -> None:
    print("=" * 74)
    print(f"== {text}")
    print("=" * 74)


def _run_fig1():
    from benchmarks import fig1_trace

    _banner("fig1_trace: Vortex execution regimes (paper Fig. 1)")
    fig1 = fig1_trace.run()
    print("\nname,us_per_call,derived")
    for lws, cycles, calls, regime in fig1:
        print(f"fig1_vecadd_lws{lws},0.0,cycles={cycles};calls={calls};{regime}")
    return {"rows": [list(r) for r in fig1]}


def _run_fig2():
    from benchmarks import fig2_sweep

    _banner("fig2_sweep: 450-configuration mapping comparison (paper Fig. 2)")
    fig2 = fig2_sweep.run()
    print("\nname,us_per_call,derived")
    for name, s in fig2.items():
        if name == "_summary":
            continue
        print(f"fig2_{name},0.0,naive_avg={s['naive_avg']:.2f};"
              f"fixed_avg={s['fixed_avg']:.2f};fixed_max={s['fixed_max']:.1f}")
    s = fig2["_summary"]
    print(f"fig2_SUMMARY,0.0,naive_avg={s['naive_avg']:.2f}(paper1.3);"
          f"fixed_avg={s['fixed_avg']:.2f}(paper3.7);"
          f"tail={s['tail_max']:.1f}(paper~20)")
    return fig2


def _run_kern():
    from benchmarks import kernel_bench

    _banner("kernel_bench: Pallas kernels x mapping policies (interpret)")
    print("name,us_per_call,derived")
    return kernel_bench.run()


def _run_tuner():
    from benchmarks import tuner_bench

    _banner("tuner_bench: cache dispatch overhead + NAIVE/FIXED/AUTO/TUNED")
    return tuner_bench.run()


def _run_prof():
    from benchmarks import profiler_bench

    _banner("profiler_bench: measured-cost tuning + calibration (fixture)")
    return profiler_bench.run()


def _run_serve():
    from benchmarks import serve_bench

    _banner("serve_bench: bucketed tuned dispatch vs naive/static serving")
    return serve_bench.run()


def _run_obs():
    from benchmarks import obs_bench

    _banner("obs_bench: traced vs plain serving + feedback/drift round trip")
    return obs_bench.run()


def _run_retune():
    from benchmarks import retune_bench

    _banner("retune_bench: live A/B-guarded recovery from a poisoned plan")
    return retune_bench.run()


def _run_roof():
    from benchmarks import roofline_table

    _banner("roofline: dry-run derived terms (see EXPERIMENTS.md)")
    for mesh in ("single", "multi"):
        roofline_table.run(mesh=mesh)
        print()


SECTIONS = {
    "fig1": _run_fig1,
    "fig2": _run_fig2,
    "kern": _run_kern,
    "tuner": _run_tuner,
    "prof": _run_prof,
    "serve": _run_serve,
    "obs": _run_obs,
    "retune": _run_retune,
    "roof": _run_roof,
}


class _Tee(io.TextIOBase):
    """Mirror section output to the real stdout while keeping a copy so
    the consolidated JSON can carry the CSV rows verbatim."""

    def __init__(self, *streams):
        self.streams = streams

    def write(self, s):
        for st in self.streams:
            st.write(s)
        return len(s)

    def flush(self):
        for st in self.streams:
            st.flush()


def _jsonable(obj):
    """Best-effort JSON sanitizer for section return values (tuples,
    numpy scalars, dataclass-ish objects) — drop what won't serialize
    rather than failing the whole consolidation."""
    if obj is None or isinstance(obj, (bool, int, float, str)):
        return obj
    if isinstance(obj, dict):
        return {str(k): _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple, set)):
        return [_jsonable(v) for v in obj]
    for attr in ("item", "as_dict"):           # numpy scalar / summary
        fn = getattr(obj, attr, None)
        if callable(fn):
            try:
                return _jsonable(fn())
            except Exception:
                pass
    return str(obj)


def _csv_rows(text: str) -> list[str]:
    """The ``name,value,derived`` rows a section streamed (banners,
    headers, and prose filtered out)."""
    rows = []
    for line in text.splitlines():
        line = line.strip()
        if ("," in line and not line.startswith(("=", "#"))
                and line != "name,us_per_call,derived"):
            rows.append(line)
    return rows


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    names = argv or list(SECTIONS)
    unknown = [n for n in names if n not in SECTIONS]
    if unknown:
        print(f"unknown sections {unknown}; available: {list(SECTIONS)}",
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    results = {}
    for i, name in enumerate(names):
        if i:
            print()
        buf = io.StringIO()
        with contextlib.redirect_stdout(_Tee(sys.stdout, buf)):
            ret = SECTIONS[name]()
        results[name] = {"summary": _jsonable(ret),
                         "rows": _csv_rows(buf.getvalue())}
    out = os.environ.get("REPRO_BENCH_JSON", "BENCH_results.json")
    with open(out, "w") as f:
        json.dump({"sections": results, "argv": names}, f,
                  indent=2, sort_keys=True)
    print(f"\n[bench] consolidated results -> {out} "
          f"({len(results)} sections)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
