"""Observability subsystem invariants (``repro.obs``).

Three layers of guarantees:

  * the ``Tracer`` itself — span nesting/parentage under an injected
    clock, the bounded ring, thread-safe counters, the ambient
    null-tracer protocol;
  * the export round trip — versioned JSONL (schema-skew rejection,
    torn-line tolerance) and the Chrome/Perfetto form;
  * the serving integration — every decode tick / prefill admit span
    carries its bucket key and EXECUTED plan, the feedback loop lands
    replayable ``source="measured"`` records in a profiler TraceStore,
    the drift report ranks buckets, and (the critical one) attaching a
    tracer leaves the engine's lowered decode HLO byte-identical —
    tracing is host-side bookkeeping that never enters jitted code.
"""

import json
import math
import threading

import pytest

from repro.obs import (NULL_TRACER, OBS_SCHEMA_VERSION, NullTracer, Tracer,
                       aggregate, chrome_trace, drift_report, get_tracer,
                       load_trace, set_tracer, using_tracer, write_trace)


class FakeClock:
    """Deterministic injectable clock: advances by ``step`` per read."""

    def __init__(self, step=1.0):
        self.t = 0.0
        self.step = step

    def __call__(self):
        self.t += self.step
        return self.t


# --------------------------------------------------------------------------- #
# Tracer core
# --------------------------------------------------------------------------- #


class TestTracer:
    def test_span_records_duration_from_injected_clock(self):
        tr = Tracer(clock=FakeClock(step=1.0))
        with tr.span("work", bucket=64):
            pass
        (rec,) = tr.spans()
        assert rec.name == "work"
        assert rec.attrs == {"bucket": 64}
        assert rec.dur == 1.0          # exactly one clock step inside
        assert rec.parent is None
        assert rec.t1 == rec.t0 + rec.dur

    def test_nested_spans_record_parentage(self):
        tr = Tracer(clock=FakeClock())
        with tr.span("outer") as outer:
            with tr.span("inner"):
                pass
            tr.instant("point")
        inner, point, outer_rec = tr.spans()
        assert [r.name for r in tr.spans()] == ["inner", "point", "outer"]
        assert inner.parent == outer.sid
        assert point.parent == outer.sid
        assert point.dur == 0.0
        assert outer_rec.parent is None
        # sids are unique and the ring is close-ordered (inner first)
        assert len({r.sid for r in tr.spans()}) == 3

    def test_set_attaches_attrs_to_open_span(self):
        tr = Tracer(clock=FakeClock())
        with tr.span("resolve", kernel="vecadd") as sp:
            sp.set(source="cache", probes=0)
        (rec,) = tr.spans()
        assert rec.attrs == {"kernel": "vecadd", "source": "cache",
                             "probes": 0}

    def test_ring_is_bounded_oldest_evicted(self):
        tr = Tracer(clock=FakeClock(), capacity=4)
        for i in range(10):
            tr.instant("ev", i=i)
        assert len(tr) == 4
        assert [r.attrs["i"] for r in tr.spans()] == [6, 7, 8, 9]

    def test_capacity_must_be_positive(self):
        with pytest.raises(ValueError):
            Tracer(capacity=0)

    def test_counters_are_thread_safe(self):
        tr = Tracer()
        n_threads, n_inc = 8, 2000

        def work():
            for _ in range(n_inc):
                tr.count("ticks")

        ts = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert tr.counters() == {"ticks": n_threads * n_inc}

    def test_gauge_keeps_last_value(self):
        tr = Tracer()
        tr.gauge("live", 1)
        tr.gauge("live", 3)
        assert tr.gauges() == {"live": 3}

    def test_clear_keeps_meta(self):
        tr = Tracer(clock=FakeClock(), meta={"arch": "x"})
        tr.instant("a")
        tr.count("c")
        tr.clear()
        assert len(tr) == 0 and tr.counters() == {}
        assert tr.meta == {"arch": "x"}


class TestNullTracerProtocol:
    def test_ambient_default_is_null(self):
        assert get_tracer() is NULL_TRACER
        assert not get_tracer().enabled

    def test_using_tracer_installs_and_restores(self):
        tr = Tracer()
        assert get_tracer() is NULL_TRACER
        with using_tracer(tr):
            assert get_tracer() is tr
        assert get_tracer() is NULL_TRACER

    def test_using_tracer_restores_on_exception(self):
        tr = Tracer()
        with pytest.raises(RuntimeError):
            with using_tracer(tr):
                raise RuntimeError("boom")
        assert get_tracer() is NULL_TRACER

    def test_set_tracer_none_resets_to_null(self):
        set_tracer(Tracer())
        try:
            assert get_tracer() is not NULL_TRACER
        finally:
            set_tracer(None)
        assert get_tracer() is NULL_TRACER

    def test_null_tracer_is_inert(self):
        t = NullTracer()
        with t.span("anything", x=1) as sp:
            sp.set(y=2)
        t.instant("e")
        t.count("c", 5)
        t.gauge("g", 1)
        t.meta["k"] = "v"              # writes never stick
        assert t.spans() == [] and t.counters() == {} and t.meta == {}
        assert len(t) == 0


# --------------------------------------------------------------------------- #
# Export round trip
# --------------------------------------------------------------------------- #


def _sample_tracer():
    tr = Tracer(clock=FakeClock(), meta={"arch": "toy", "layers": 2})
    with tr.span("decode_tick", bucket=64, decode_block=128,
                 paged_decode_block=32, tiles=(32, 128)):
        pass
    tr.instant("pool_grow", kv_len=128)
    tr.count("pool_growths", 3)
    tr.gauge("queue_depth", 2)
    return tr


class TestExport:
    def test_jsonl_round_trip(self, tmp_path):
        tr = _sample_tracer()
        path = write_trace(tr, str(tmp_path / "t.jsonl"))
        back = load_trace(path)
        assert back.meta == {"arch": "toy", "layers": 2}
        assert back.counters() == {"pool_growths": 3}
        assert back.gauges() == {"queue_depth": 2}
        a, b = tr.spans(), back.spans()
        assert [r.name for r in b] == [r.name for r in a]
        assert [r.sid for r in b] == [r.sid for r in a]
        assert [r.parent for r in b] == [r.parent for r in a]
        assert b[0].dur == a[0].dur
        assert b[0].attrs["bucket"] == 64
        # JSON has no tuples: tuple attrs come back as lists
        assert b[0].attrs["tiles"] == [32, 128]

    def test_jsonl_header_first_line(self, tmp_path):
        path = write_trace(_sample_tracer(), str(tmp_path / "t.jsonl"))
        header = json.loads(open(path).readline())
        assert header["kind"] == "repro-obs-trace"
        assert header["version"] == OBS_SCHEMA_VERSION
        assert header["meta"]["arch"] == "toy"

    def test_version_skew_rejected(self, tmp_path):
        path = write_trace(_sample_tracer(), str(tmp_path / "t.jsonl"))
        lines = open(path).read().splitlines()
        header = json.loads(lines[0])
        header["version"] = OBS_SCHEMA_VERSION + 1
        lines[0] = json.dumps(header)
        (tmp_path / "skew.jsonl").write_text("\n".join(lines))
        with pytest.raises(ValueError, match="version"):
            load_trace(str(tmp_path / "skew.jsonl"))

    def test_wrong_kind_rejected(self, tmp_path):
        p = tmp_path / "other.jsonl"
        p.write_text('{"version": 1, "kind": "something-else"}\n')
        with pytest.raises(ValueError, match="not a"):
            load_trace(str(p))

    def test_torn_lines_skipped_not_fatal(self, tmp_path):
        path = write_trace(_sample_tracer(), str(tmp_path / "t.jsonl"))
        with open(path, "a") as f:
            f.write('{"type": "span", "name": "torn", "t0": ')  # torn write
        back = load_trace(path)
        assert [r.name for r in back.spans()] == ["decode_tick", "pool_grow"]

    def test_chrome_trace_shape(self):
        doc = chrome_trace(_sample_tracer())
        by_ph = {}
        for ev in doc["traceEvents"]:
            by_ph.setdefault(ev["ph"], []).append(ev)
        (span,) = by_ph["X"]
        assert span["name"] == "decode_tick"
        assert span["dur"] == pytest.approx(1e6)     # 1s clock step in us
        assert span["args"]["bucket"] == 64
        (inst,) = by_ph["i"]
        assert inst["name"] == "pool_grow"
        assert {ev["name"] for ev in by_ph["C"]} == \
            {"pool_growths", "queue_depth"}
        assert doc["otherData"] == {"arch": "toy", "layers": 2}

    def test_chrome_json_round_trip(self, tmp_path):
        tr = _sample_tracer()
        path = write_trace(tr, str(tmp_path / "t.json"))
        back = load_trace(path)
        assert back.meta == {"arch": "toy", "layers": 2}
        names = [r.name for r in back.spans()]
        assert "decode_tick" in names and "pool_grow" in names
        dt = next(r for r in back.spans() if r.name == "decode_tick")
        assert dt.attrs["decode_block"] == 128
        assert dt.dur == pytest.approx(1.0)

    def test_empty_file_rejected(self, tmp_path):
        p = tmp_path / "empty.jsonl"
        p.write_text("")
        with pytest.raises(ValueError, match="empty"):
            load_trace(str(p))


# --------------------------------------------------------------------------- #
# Serving integration: spans -> feedback -> drift, and the HLO pin
# --------------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def traced_run():
    """One traced reduced-model serving run shared by the integration
    tests (engine construction + XLA compiles dominate the cost)."""
    from repro.serve import ServeEngine
    from repro.tuner import TuningCache

    tracer = Tracer()
    eng = ServeEngine("smollm-135m", slots=2, max_len=32, reduced=True,
                      tracer=tracer, tuning_cache=TuningCache(path=None),
                      prefill_chunk=None, verbose=False)
    for i, (plen, out) in enumerate([(4, 3), (7, 2), (5, 4), (3, 2)]):
        eng.submit(list(range(1, plen + 1)), max_new_tokens=out,
                   arrival=0.01 * i)
    eng.run()
    return tracer, eng


class TestServingSpans:
    def test_every_decode_tick_carries_bucket_and_executed_plan(
            self, traced_run):
        tracer, eng = traced_run
        ticks = [s for s in tracer.spans() if s.name == "decode_tick"]
        assert ticks, "run produced no decode ticks"
        for s in ticks:
            assert s.attrs["bucket"] == eng.pool.kv_len
            assert s.attrs["decode_block"], s.attrs
            # fused paged decode is the default: block_s must ride along
            assert s.attrs["paged_decode_block"], s.attrs
            assert 0 < s.attrs["live"] <= s.attrs["slots"]

    def test_every_prefill_carries_bucket_and_tiles(self, traced_run):
        tracer, _ = traced_run
        pres = [s for s in tracer.spans() if s.name == "prefill"]
        assert len(pres) == 4          # one per admitted request
        for s in pres:
            assert s.attrs["bucket"] >= s.attrs["prompt_len"]
            bq, bkv = s.attrs["tiles"]
            assert bq >= 1 and bkv >= 1

    def test_resolution_spans_nest_and_attribute(self, traced_run):
        tracer, _ = traced_run
        names = {s.name for s in tracer.spans()}
        assert {"bucket_resolve", "resolve_plan", "slot_recycle"} <= names
        cold = [s for s in tracer.spans() if s.name == "bucket_resolve"
                and s.attrs.get("provenance") == "cold"]
        assert cold, "no cold bucket resolution recorded"
        # dispatch spans opened during the cold resolve nest under it
        nested = [s for s in tracer.spans() if s.name == "resolve_plan"
                  and s.parent in {c.sid for c in cold}]
        assert nested, "resolve_plan spans did not nest under the bucket"

    def test_counters_and_meta(self, traced_run):
        tracer, eng = traced_run
        spans = tracer.spans()
        steps = {s.sid for s in spans if s.name == "step"}
        # four admissions, at least one decode tick, and each tick's
        # sample decoded at least one row
        assert len([s for s in spans if s.name == "prefill"]) == 4
        ticks = [s for s in spans if s.name == "decode_tick"]
        decoded = [s.attrs["rows"] for s in spans
                   if s.name == "sample" and s.parent in steps]
        assert len(ticks) >= 1 and len(decoded) == len(ticks)
        assert sum(decoded) >= len(ticks)
        assert sum(decoded) == sum(st.live for st in eng.metrics.steps)
        assert tracer.counters().get("pool_growths", 0) == eng.pool_growths
        assert tracer.gauges() == {}
        m = tracer.meta
        assert m["layers"] == eng.cfg.num_layers
        assert m["head_dim"] == eng.cfg.head_dim
        assert m["hw"] == eng.router.hw.name
        assert m["paged"] and m["fused_decode"]

    def test_aggregate_groups_by_bucket_and_kernel(self, traced_run):
        tracer, _ = traced_run
        rows = aggregate(tracer.spans())
        phases = {(r.phase, r.kernel) for r in rows}
        assert ("decode", "paged_decode") in phases
        assert ("prefill", "flash_attention") in phases
        for r in rows:
            assert r.n == len(r.samples)
            assert r.total_s == pytest.approx(sum(r.samples))
            assert r.median_s <= r.total_s


class TestEngineLoopSpans:
    """Every iteration of ``run()``'s loop is a ``step`` span, with the
    iteration's phases nested in it."""

    @staticmethod
    def _ancestors(spans):
        by_sid = {s.sid: s for s in spans}

        def chain(s):
            out = []
            while s.parent is not None:
                s = by_sid[s.parent]
                out.append(s.name)
            return out
        return chain

    def test_one_step_per_iteration_with_phases_nested(self, traced_run):
        tracer, _ = traced_run
        spans = tracer.spans()
        chain = self._ancestors(spans)
        steps = [s for s in spans if s.name == "step"]
        admits = [s for s in spans if s.name == "admit"]
        # every iteration admits (or finds nothing to admit) first
        assert steps and len(admits) == len(steps)
        assert all(s.parent is None for s in steps)
        for name in ("admit", "sample", "retire", "report", "decode_tick",
                     "prefill", "write_row", "wait"):
            found = [s for s in spans if s.name == name]
            assert found, name
            assert all("step" in chain(s) for s in found), name
        # the run's report ends its last iteration
        (report,) = [s for s in spans if s.name == "report"]
        assert report.parent == max(steps, key=lambda s: s.t0).sid
        # each device wait is inside the step call that dispatched it
        for w in (s for s in spans if s.name == "wait"):
            assert chain(w)[0] in ("prefill", "decode_tick",
                                   "prefill_chunk")

    def test_decode_tick_carries_context_and_pool_length(self, traced_run):
        tracer, eng = traced_run
        ticks = [s for s in tracer.spans() if s.name == "decode_tick"]
        for s in ticks:
            assert s.attrs["pool_len"] == s.attrs["bucket"]
            assert 0 < s.attrs["ctx_tokens"] <= \
                s.attrs["slots"] * s.attrs["pool_len"]
        # a tick that makes a request's token k (k >= 1; token 0 comes
        # from its prefill) reads its prompt and k tokens: requests
        # (4, 3), (7, 2), (5, 4), (3, 2) read 5+6, 8, 6+7+8, 4
        assert sum(s.attrs["ctx_tokens"] for s in ticks) == 44
        # each of those seven rows lies in one 16-position page
        assert sum(s.attrs["pages"] for s in ticks) == 7

    def test_chunked_prefill_phases(self):
        from repro.serve import ServeEngine
        from repro.tuner import TuningCache

        tracer = Tracer()
        eng = ServeEngine("smollm-135m", slots=2, max_len=64, reduced=True,
                          tracer=tracer, tuning_cache=TuningCache(path=None),
                          prefill_chunk=8, verbose=False)
        eng.submit(list(range(1, 20)), max_new_tokens=3)
        eng.run()
        spans = tracer.spans()
        chain = self._ancestors(spans)
        chunks = [s for s in spans if s.name == "prefill_chunk"]
        assert len(chunks) == 3                       # 19 tokens, 8 a chunk
        waits = [s for s in spans if s.name == "wait"
                 and chain(s)[0] == "prefill_chunk"]
        assert len(waits) == len(chunks)
        (row,) = [s for s in spans if s.name == "write_row"]
        assert chain(row) == ["step"] and row.attrs["prompt_len"] == 19
        # the first token is sampled off the last chunk, the other two
        # after decode ticks that read 19 + 1 and 19 + 2 positions
        ticks = [s for s in spans if s.name == "decode_tick"]
        assert [t.attrs["ctx_tokens"] for t in ticks] == [20, 21]
        # both span two 16-position pages: what the fused read fetches
        assert [t.attrs["pages"] for t in ticks] == [2, 2]


class TestProfilerAnnotations:
    """While the JAX profiler records, an enabled tracer's spans enter
    the profiler trace as ``serve.<name>`` annotations with their
    attributes; the null tracer writes nothing there."""

    def test_span_in_profiler_trace(self, tmp_path):
        import glob

        import jax
        from jax.profiler import ProfileData

        tracer = Tracer()
        jax.profiler.start_trace(str(tmp_path))
        try:
            with tracer.span("decode_tick", bucket=64, tiles=(32, 128),
                             paged_decode_block=None) as sp:
                with tracer.span("wait"):
                    jax.numpy.ones(8).sum().block_until_ready()
                sp.set(rows=3)
            with NULL_TRACER.span("null_span", bucket=1):
                pass
            tracer.instant("pool_grow", kv_len=128)
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True)
        evs = [ev for plane in ProfileData.from_file(path).planes
               for line in plane.lines for ev in line.events
               if ev.name.startswith("serve.")]
        by_name = {ev.name: ev for ev in evs}
        assert sorted(by_name) == ["serve.decode_tick", "serve.wait"]
        assert len(evs) == 2          # no null span, no instant
        tick = by_name["serve.decode_tick"]
        stats = {k: v for k, v in tick.stats}
        assert stats == {"bucket": 64, "tiles": "(32, 128)",
                         "paged_decode_block": "None", "rows": 3}
        wait = by_name["serve.wait"]
        assert tick.start_ns <= wait.start_ns
        assert wait.end_ns <= tick.end_ns
        # the records stay on the tracer's clock, attributes as given
        rec = next(r for r in tracer.spans() if r.name == "decode_tick")
        assert rec.attrs["tiles"] == (32, 128)

    def test_no_annotation_while_profiler_off(self):
        from jax.profiler import TraceAnnotation

        assert not TraceAnnotation.is_enabled()
        tracer = Tracer()
        with tracer.span("step") as sp:
            assert sp._ann is None


class TestFeedbackLoop:
    def test_feedback_lands_replayable_measured_records(self, traced_run,
                                                        tmp_path):
        from repro.obs import feedback_to_store
        from repro.obs.feedback import _kernel_desc
        from repro.profiler import TraceStore
        from repro.profiler.cost import hybrid_refine

        tracer, eng = traced_run
        store = TraceStore(str(tmp_path / "serving.jsonl"), autosave=False)
        n = feedback_to_store(tracer.spans(), tracer.meta, eng.router.hw,
                              store)
        assert n > 0
        store.save()
        for m in store.records():
            assert m.source == "serving"
            assert m.median_s > 0

        rows = [r for r in aggregate(tracer.spans()) if r.phase == "decode"]
        ob = max(rows, key=lambda r: r.n)
        replay = TraceStore(str(tmp_path / "serving.jsonl"))
        res = hybrid_refine(ob.kernel, _kernel_desc(ob, tracer.meta),
                            eng.router.hw, store=replay, mode="cached")
        # the engine executed the roofline winner, so the serving record
        # IS among the survivors: the replay must land on measurement
        assert res.source == "measured"
        assert res.value == ob.value

    def test_drift_report_ranks_buckets(self, traced_run):
        tracer, eng = traced_run
        rep = drift_report(tracer.spans(), tracer.meta, eng.router.hw)
        assert rep.rows, "no drift rows from a traced run"
        assert rep.median_ratio > 0
        mags = [abs(math.log(r.drift)) for r in rep.rows]
        assert mags == sorted(mags, reverse=True), "rows not ranked"
        for r in rep.rows:
            assert r.ratio == pytest.approx(r.measured_s / r.predicted_s)
        # fleet-median normalization: a 10x threshold keeps only rows
        # genuinely far off the fleet, and the formatted table parses
        assert all(abs(math.log(c.drift)) > math.log(10.0)
                   for c in rep.candidates(threshold=10.0))
        assert "drift" in rep.format()

    def test_drift_empty_without_meta(self, traced_run):
        tracer, eng = traced_run
        rep = drift_report(tracer.spans(), {}, eng.router.hw)
        assert rep.rows == ()


class TestTracingNeverEntersJit:
    def test_decode_hlo_byte_identical_with_and_without_tracer(self):
        """THE overhead guarantee: a traced engine lowers the exact same
        decode step as an untraced one — spans wrap host-side around
        the jitted call, so XLA never sees the difference."""
        import jax
        import jax.numpy as jnp

        from repro.serve import ServeEngine
        from repro.tuner import TuningCache

        def build(tracer):
            return ServeEngine("smollm-135m", slots=2, max_len=32,
                               reduced=True, tracer=tracer,
                               tuning_cache=TuningCache(path=None),
                               verbose=False)

        plain, traced = build(None), build(Tracer())
        assert not plain.obs.enabled and traced.obs.enabled
        tables = jnp.asarray(plain._tables)
        args = dict(decode_block=128, page_tables=tables,
                    page_block=plain._block_size, paged_decode_block=16)
        hlo_plain = plain._decode.lower(
            plain.params, dict(plain._cache),
            jnp.asarray(plain._tokens), **args).as_text()
        hlo_traced = traced._decode.lower(
            plain.params, dict(traced._cache),
            jnp.asarray(traced._tokens), **args).as_text()
        assert hlo_plain == hlo_traced, \
            "attaching a tracer changed the lowered decode step"


# --------------------------------------------------------------------------- #
# trace_view CLI
# --------------------------------------------------------------------------- #


class TestTraceViewCLI:
    @pytest.fixture()
    def trace_view(self):
        import importlib.util
        import os

        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "trace_view.py")
        spec = importlib.util.spec_from_file_location("trace_view", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_renders_traced_serving_run(self, trace_view, traced_run,
                                        tmp_path, capsys):
        tracer, _ = traced_run
        path = write_trace(tracer, str(tmp_path / "serve.json"))
        rc = trace_view.main([path, "--require-buckets", "--require-drift"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "decode,32,paged_decode" in out
        assert "drift vs roofline" in out

    def test_require_flags_fail_on_bare_trace(self, trace_view, tmp_path,
                                              capsys):
        bare = Tracer(clock=FakeClock())
        with bare.span("unrelated"):
            pass
        path = write_trace(bare, str(tmp_path / "bare.jsonl"))
        assert trace_view.main([path]) == 0
        assert trace_view.main([path, "--require-buckets"]) == 1
        capsys.readouterr()
