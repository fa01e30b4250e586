"""Observability benchmark: traced vs untraced serving on identical
traffic — device-free (CPU, reduced model), self-asserting.

Two engines serve the SAME synthetic mixes (Poisson arrivals, ragged
prompt/output lengths): one plain, one with a ``repro.obs.Tracer``
attached.  Each engine gets a warmup pass (compiles + refines), the
tracer is then cleared so feedback/drift see only steady-state spans,
and four fresh mixes run through both engines with the order
alternating per mix.

Acceptance (asserted):
  * tracing never changes serving semantics: both engines complete
    the same requests at the same output lengths on every mix (spans
    never enter jitted code — the instrumentation is host-side
    bookkeeping around the same compiled steps; ``tests/test_obs.py``
    pins the decode HLO byte-identical);
  * tracing is effectively free: the per-tick instrumentation cost
    (one attributed span + two counters + one gauge, timed directly
    over 20k iterations) is under 3% of the median traced
    ``decode_tick`` duration.  This is the honest form of the overhead
    bound — wall-clock A/B of sub-second passes on a shared CI box is
    dominated by scheduling noise, so the A/B throughput is reported
    but not asserted;
  * every ``decode_tick`` span carries its bucket key AND the executed
    plan (``decode_block`` + the fused ``paged_decode_block``), every
    ``prefill`` span carries its prompt bucket and executed flash
    tiles — the attribution the feedback loop runs on;
  * the serving feedback lands in a profiler ``TraceStore`` under the
    engine's real hardware key and is REPLAYABLE: ``hybrid_refine``
    over the serving-fed store resolves with ``source="measured"`` at
    the value the engine actually executed;
  * the drift report ranks at least one measured-vs-roofline row.

Set ``REPRO_OBS_TRACE=/path/trace.json`` to keep the traced pass's
Perfetto/Chrome trace (the CI benchmark job uploads it and asserts it
with ``tools/trace_view.py --require-buckets --require-drift``).

    PYTHONPATH=src python -m benchmarks.obs_bench
"""

from __future__ import annotations

import dataclasses
import os
import statistics
import tempfile
import time

from repro.configs.base import get_config
from repro.serve import ServeEngine, TrafficConfig, drive
from repro.tuner import TuningCache

MAX_LEN = 256
SLOTS = 4

_BASE = dict(n_requests=20, rate=200.0, mode="open",
             prompt_dist=("uniform", 4, 56),
             output_dist=("uniform", 2, 16), vocab=512)
WARMUP = TrafficConfig(seed=0, **_BASE)
#: tiny prompts so decode ticks at the SMALLEST pool bucket compile
#: during warmup too — the main mix's prefills grow the pool past it
#: before any decode runs, leaving that shape cold otherwise
WARMUP_SMALL = TrafficConfig(seed=0, **{**_BASE, "n_requests": 6,
                                        "prompt_dist": ("uniform", 2, 8),
                                        "output_dist": ("uniform", 4, 8)})
#: four fresh steady-state mixes; run order alternates per mix so both
#: engines sample every position equally (see run())
MEASURED = tuple(TrafficConfig(seed=s, **_BASE) for s in (1, 11, 21, 31))

#: per-tick tracer cost must stay under this fraction of a median tick
OVERHEAD_BUDGET = 0.03
_COST_ITERS = 20_000


def _cfg():
    return dataclasses.replace(get_config("smollm-135m").reduced(),
                               dtype="float32")


def _one_pass(eng, traffic):
    """One steady-state mix on a warm engine — reset first so the
    metrics (and pool state) are per-mix while jit caches and bucket
    plans stay warm.  Returns (tokens_per_s, outputs)."""
    eng.reset()
    report = drive(eng, traffic)
    s = report.summary
    assert s.n_completed == traffic.n_requests, "requests starved"
    return s.tokens_per_s, report.outputs


def _tick(t) -> None:
    """The spans ``ServeEngine._decode_tick`` opens per step: the tick
    (7 attributes) with its device wait nested, then the sample."""
    with t.span("decode_tick", bucket=128, decode_block=128,
                paged_decode_block=16, live=4, slots=4, ctx_tokens=300,
                pool_len=128):
        with t.span("wait"):
            pass
    with t.span("sample") as sp:
        sp.set(rows=4)


def _tick_cost_s() -> float:
    """Directly time one decode tick's worth of instrumentation on a
    fresh Tracer — exactly the spans ``ServeEngine._decode_tick`` opens
    per step."""
    from repro.obs import Tracer

    t = Tracer(capacity=3 * _COST_ITERS + 16)
    # warm the span paths before timing
    for _ in range(100):
        _tick(t)
    t.clear()
    t0 = time.perf_counter()
    for _ in range(_COST_ITERS):
        _tick(t)
    return (time.perf_counter() - t0) / _COST_ITERS


def _assert_span_attribution(spans) -> dict:
    """Every decode tick and prefill admit must be attributable: bucket
    key + the executed plan, no exceptions — a single bare span would
    silently drop work from the feedback aggregation."""
    decode = [s for s in spans if s.name == "decode_tick"]
    prefill = [s for s in spans if s.name == "prefill"]
    assert decode and prefill, "traced run produced no serving spans"
    for s in decode:
        assert s.attrs.get("bucket") and s.attrs.get("decode_block"), \
            f"unattributed decode_tick: {s.attrs}"
        assert s.attrs.get("paged_decode_block"), \
            f"fused paged decode tick without block_s: {s.attrs}"
    for s in prefill:
        assert s.attrs.get("bucket") and s.attrs.get("tiles"), \
            f"unattributed prefill: {s.attrs}"
    return {"decode_tick": len(decode), "prefill": len(prefill)}


def _feedback_round_trip(tracer, hw, print_fn) -> dict:
    """Serving spans -> Measurement records -> TraceStore file -> a
    ``hybrid_refine(mode="cached")`` replay that lands source="measured"
    at the block size the engine actually executed."""
    from repro.obs import aggregate, drift_report, feedback_to_store
    from repro.obs.feedback import _kernel_desc
    from repro.profiler import TraceStore
    from repro.profiler.cost import hybrid_refine

    spans, meta = tracer.spans(), tracer.meta
    fd, path = tempfile.mkstemp(suffix=".jsonl")
    os.close(fd)
    try:
        store = TraceStore(path, autosave=False)
        n = feedback_to_store(spans, meta, hw, store)
        store.save()
        assert n > 0, "no serving measurements reached the store"

        rows = aggregate(spans)
        decode_rows = [ob for ob in rows if ob.phase == "decode"]
        assert decode_rows, "no per-bucket decode aggregation"
        ob = max(decode_rows, key=lambda r: r.n)
        desc = _kernel_desc(ob, meta)
        replay = TraceStore(path)               # re-read from disk
        res = hybrid_refine(ob.kernel, desc, hw, store=replay,
                            mode="cached")
        assert res.source == "measured", \
            f"serving feedback not replayable: source={res.source}"
        assert res.value == ob.value, \
            (f"replay picked {res.value}, engine executed {ob.value} — "
             f"the executed plan must be its own store record")
    finally:
        os.unlink(path)

    rep = drift_report(spans, meta, hw)
    assert rep.rows, "drift report empty on a traced serving run"
    worst = rep.rows[0]
    print_fn(f"obs_feedback,0.0,store_records={n};buckets={len(rows)};"
             f"replay={res.source}@{res.value};drift_rows={len(rep.rows)};"
             f"worst_drift={worst.drift:.2f}x@{worst.kernel}/{worst.bucket}")
    return {"store_records": n, "buckets": len(rows),
            "replay_value": res.value, "drift_rows": len(rep.rows)}


def run(print_fn=print) -> dict:
    import jax

    from repro.models import build_model
    from repro.obs import Tracer, write_trace

    cfg = _cfg()
    params = build_model(cfg).init(jax.random.key(0))
    print_fn("name,us_per_call,derived")

    plain = ServeEngine(cfg, slots=SLOTS, max_len=MAX_LEN, params=params,
                        tuning_cache=TuningCache(path=None),
                        prefill_chunk=None)
    tracer = Tracer()
    traced_eng = ServeEngine(cfg, slots=SLOTS, max_len=MAX_LEN,
                             params=params, tracer=tracer,
                             tuning_cache=TuningCache(path=None),
                             prefill_chunk=None)
    # both engines warm first (compiles + plan refinement), then the
    # tracer is cleared: warmup ticks include XLA compile time at every
    # pool-growth boundary, and letting those spans reach the feedback
    # aggregation would poison the per-bucket measurements (a 5s
    # compile attributed to a 10ms bucket).  clear() keeps the engine
    # meta, so attribution context survives.
    for eng in (plain, traced_eng):
        drive(eng, WARMUP)
        eng.reset()
        drive(eng, WARMUP_SMALL)
    tracer.clear()

    # each measured mix runs through both engines with the ORDER
    # alternating per mix (the first run of a pair absorbs
    # disproportionate interference on a contended box).  Both engines
    # must complete the same requests at the same output lengths —
    # tracing must not change scheduling semantics.  Token CONTENT is
    # deliberately not compared: open-mode admission is wall-clock
    # driven, so batch composition (and thus padding and float
    # summation order) varies run-to-run, and on an untrained model
    # greedy argmax flips on those near-ties; the compute-identity
    # guarantee is the byte-identical decode HLO pin in
    # tests/test_obs.py.  Throughput is reported for trend tracking but
    # NOT asserted: sub-second wall-clock A/B on a shared CI core is
    # scheduling noise; the asserted overhead bound is the direct
    # per-tick instrumentation cost below.
    plain_tok, traced_tok = [], []
    for i, traffic in enumerate(MEASURED):
        order = (plain, traced_eng) if i % 2 == 0 else (traced_eng, plain)
        outs = {}
        for eng in order:
            tok, outputs = _one_pass(eng, traffic)
            (plain_tok if eng is plain else traced_tok).append(tok)
            # rids are globally monotonic across engines; compare the
            # per-request output lengths in submission order instead
            outs[id(eng)] = [len(t) for _, t in sorted(outputs.items())]
        assert outs[id(plain)] == outs[id(traced_eng)], \
            f"mix {i}: traced and plain output-length sequences diverge"

    tp = max(plain_tok)
    tt = max(traced_tok)
    ratio = tt / max(tp, 1e-9)
    counts = _assert_span_attribution(tracer.spans())

    # the asserted overhead bound: per-tick instrumentation cost vs the
    # median duration of a real (steady-state) traced decode tick
    tick_med = statistics.median(s.dur for s in tracer.spans()
                                 if s.name == "decode_tick")
    cost = _tick_cost_s()
    overhead = cost / tick_med
    passes = ";".join(f"pass{i}={p:.0f}/{t:.0f}" for i, (p, t)
                      in enumerate(zip(plain_tok, traced_tok)))
    print_fn(f"obs_overhead,{cost * 1e6:.3f},"
             f"overhead_pct={overhead * 100:.3f};"
             f"tick_med_us={tick_med * 1e6:.0f};"
             f"plain_tok_s={tp:.1f};traced_tok_s={tt:.1f};"
             f"ratio={ratio:.3f};{passes};spans={len(tracer.spans())};"
             f"decode_spans={counts['decode_tick']};"
             f"prefill_spans={counts['prefill']}")
    assert overhead < OVERHEAD_BUDGET, \
        (f"tracing overhead: {cost * 1e6:.1f}us per tick vs "
         f"{tick_med * 1e6:.0f}us median tick "
         f"({overhead * 100:.2f}% >= {OVERHEAD_BUDGET * 100:.0f}%)")

    feedback = _feedback_round_trip(tracer, traced_eng.router.hw, print_fn)

    trace_path = os.environ.get("REPRO_OBS_TRACE")
    if trace_path:
        write_trace(tracer, trace_path)
        print_fn(f"obs_trace,0.0,path={trace_path};"
                 f"spans={len(tracer.spans())}")

    return {
        "plain_tok_s": tp,
        "traced_tok_s": tt,
        "ab_ratio": ratio,
        "tick_cost_us": cost * 1e6,
        "tick_median_us": tick_med * 1e6,
        "overhead_pct": overhead * 100,
        "spans": len(tracer.spans()),
        "span_counts": counts,
        **feedback,
    }


if __name__ == "__main__":
    run()
