"""The engine's spans on the profiler's clock (``harness.program``) and
the metrics that read them, on hand-built runs and on traces recorded
on a v5e (``data/``)."""

import json
import types
from pathlib import Path

import pytest

from harness import program, spec, trace
from repro.obs.trace import SpanRecord

MS = 1_000_000
TPU = ' custom-call(...), custom_call_target="tpu_custom_call"'
DATA = Path(__file__).parent / "data"
OLD = DATA / "trace_v5e_82ms.json"
STEP = DATA / "trace_v5e_323ms_spans.json"
#: tracer clock (seconds) = profiler clock (ns) / 1e9 + T0
T0 = 5.0


def reader(name):
    return spec.load_module(spec.metric_module_path(name))


def span(sid, name, a_ms, b_ms, parent=None, **attrs):
    return SpanRecord(name=name, t0=T0 + a_ms / 1e3, dur=(b_ms - a_ms) / 1e3,
                      attrs=attrs, sid=sid, parent=parent, tid=1)


def hand_built_trace():
    # harness spans: two engine steps around a sleep; the device runs
    # the decode step at 10-30 and 20-50 ms (40 ms busy, the paged
    # decode kernel in it) and a flash kernel at 60-70 ms
    return {
        "devices": {"/device:TPU:0": [
            ["%fusion.1 = bf16[64]", 10 * MS, 20 * MS, ""],
            ["%paged_decode_attention.2 = bf16[4,3,3,64]" + TPU, 20 * MS,
             30 * MS, ""],
            ["%flash_attention.3 = bf16[3,3,256,64]", 60 * MS, 10 * MS, ""],
        ]},
        "host": [["bench.run_step", 0, 55 * MS],
                 ["bench.sleep", 55 * MS, 58 * MS],
                 ["bench.run_step", 58 * MS, 100 * MS]],
    }


def hand_built_spans():
    return [
        span(2, "admit", 1, 6, 1),
        span(4, "wait", 9, 51, 3),
        span(3, "decode_tick", 8, 52, 1, ctx_tokens=300, slots=4,
             pool_len=256),
        span(5, "sample", 52, 53.5, 1, rows=3),
        span(6, "retire", 53.5, 54, 1),
        span(1, "step", 1, 54),
        span(8, "admit", 59, 62, 7),
        span(10, "wait", 63, 71, 9),
        span(9, "prefill_chunk", 62, 72, 7),
        span(11, "report", 72, 99, 7),
        span(7, "step", 59, 99),
        # after the profiler stopped, outside the window
        span(12, "decode_tick", 2000, 2001, None, ctx_tokens=900, slots=4,
             pool_len=256),
    ]


def hand_built_run(spans=None, tr=True):
    window = types.SimpleNamespace(origin=T0 - 1.0, closed=T0 + 1.0,
                                   traced=(T0, T0 + 0.1))
    return types.SimpleNamespace(
        trace=hand_built_trace() if tr else None,
        traced=window.traced if tr else None,
        spans=hand_built_spans() if spans is None else spans,
        window=window)


def test_events_on_the_profiler_clock():
    evs = program.events(hand_built_run())
    got = {(n, round(a / MS, 3), round(b / MS, 3)) for n, a, b, _ in evs}
    assert ("serve.step", 1.0, 54.0) in got
    assert ("serve.wait", 63.0, 71.0) in got
    assert ("serve.report", 72.0, 99.0) in got
    assert all(n != "serve.decode_tick" or a < 100 for n, a, _ in got)
    assert [e[1] for e in evs] == sorted(e[1] for e in evs)
    tick = next(e for e in evs if e[0] == "serve.decode_tick")
    assert tick[3]["ctx_tokens"] == 300


def test_events_need_steps_a_trace_and_matching_counts():
    no_steps = [s for s in hand_built_spans() if s.name != "step"]
    assert program.events(hand_built_run(no_steps)) is None
    assert program.events(hand_built_run(tr=False)) is None
    one_step = [s for s in hand_built_spans() if s.sid != 7]
    assert program.events(hand_built_run(one_step)) is None


def test_idle_paths_label_nested_spans_total_unchanged():
    ex = hand_built_trace()
    paths = program.idle_paths(ex, program.events(hand_built_run()))
    # gaps 0-10 ms (midpoint in the admission), 50-60 ms (in the
    # harness's sleep) and 70-100 ms (in the report)
    assert paths == pytest.approx({
        "bench.run_step > serve.step > serve.admit": 0.010,
        "bench.sleep": 0.010,
        "bench.run_step > serve.step > serve.report": 0.030})
    assert sum(paths.values()) == pytest.approx(
        sum(trace.idle_gaps(ex).values()))


@pytest.mark.parametrize("which", ["hand-built", "recorded"])
def test_idle_paths_without_engine_spans_are_todays_labels(which):
    ex = hand_built_trace() if which == "hand-built" else \
        json.loads(OLD.read_text())
    assert program.idle_paths(ex, []) == trace.idle_gaps(ex)


def test_old_recording_reduces_as_before():
    rec = json.loads(OLD.read_text())
    assert trace.window_of(rec) == (0, 82 * MS)
    assert trace.busy_s(rec) == pytest.approx(0.074236204)
    ops = trace.op_seconds(rec)
    assert len(ops) == 145
    assert sum(ops.values()) == pytest.approx(0.074236204)
    assert trace.kernel_seconds(rec, lambda d: d == (64, 3, 3, 64)) == \
        (pytest.approx(0.014738378), 2, ["%closed_call.17"])
    flash = trace.kernel_seconds(
        rec, lambda d: len(d) == 4 and d[:2] == (3, 3) and d[3] == 64)
    assert flash == (pytest.approx(0.001464572), 7,
                     ["%vmap_vmap_vmap____.5"])
    assert trace.idle_gaps(rec) == {
        "bench.run_step": pytest.approx(0.007763796)}


def test_busy_inside_intervals():
    ops = program.first_chip(hand_built_trace())
    assert program.busy_ns(ops, 0, 100 * MS) == pytest.approx(50 * MS)
    assert program.busy_ns(ops, 15 * MS, 65 * MS) == pytest.approx(40 * MS)
    assert program.busy_ns(ops, 50 * MS, 60 * MS) == 0


def test_engine_idle_ms_per_step():
    # step 1 (1-54 ms): 40 ms busy of 53; step 2 (59-99): 10 of 40
    assert reader("engine_idle_ms_per_step").read(hand_built_run()) == \
        pytest.approx((13 + 30) / 2)


def test_decode_step_device_ms():
    # the tick (8-52 ms) holds the decode step's 10-50 ms and the named
    # paged decode kernel; without the name the tick is not read
    assert reader("decode_step_device_ms").read(hand_built_run()) == \
        pytest.approx(40.0)
    run = hand_built_run()
    for op in run.trace["devices"]["/device:TPU:0"]:
        op[0] = op[0].replace("paged_decode_attention", "closed_call")
    assert reader("decode_step_device_ms").read(run) is None


def test_kv_live_share():
    # the tick in the window: 300 live positions of 4 x 256
    assert reader("kv_live_share").read(hand_built_run()) == \
        pytest.approx(100 * 300 / 1024)


@pytest.mark.parametrize("name", ["engine_idle_ms_per_step",
                                  "decode_step_device_ms", "kv_live_share"])
def test_readers_silent_without_the_engine_spans(name):
    # an engine that records no step spans and no tick context
    bare = [SpanRecord(name="decode_tick", t0=T0 + 0.01, dur=0.04,
                       attrs={"slots": 4}, sid=1, parent=None, tid=1)]
    assert reader(name).read(hand_built_run(bare)) is None
    assert reader(name).read(hand_built_run([])) is None


# -- one engine iteration recorded on a v5e, with the engine's spans ------

@pytest.fixture(scope="module")
def iteration():
    return json.loads(STEP.read_text())


def test_recorded_iteration_finds_kernels_and_spans_by_name(iteration):
    names = {e[0] for e in iteration["program"]}
    assert {"serve.step", "serve.decode_tick", "serve.prefill_chunk",
            "serve.wait", "serve.sample", "serve.retire",
            "serve.report"} <= names
    ops = program.first_chip(iteration)
    decode = program.named_ops(ops, "paged_decode_attention")
    flash = program.named_ops(ops, "flash_attention")
    assert len(decode) == len(flash) == 30            # one a layer
    # the readers that match output shapes find the same operations
    secs, n, found = trace.kernel_seconds(iteration,
                                          lambda d: d == (64, 3, 3, 64))
    assert (n, found) == (30, ["%paged_decode_attention.13"])
    assert secs == pytest.approx(sum(op[2] for op in decode) / 1e9)
    secs, n, found = trace.kernel_seconds(
        iteration, lambda d: len(d) == 4 and d[:2] == (3, 3) and d[3] == 64)
    assert (n, found) == (30, ["%vmap_vmap_vmap_flash_attention___.5"])
    assert secs == pytest.approx(sum(op[2] for op in flash) / 1e9)
    # the decode kernel runs inside the decode tick, the flash kernel
    # inside the prefill chunk
    for kernel, span in ((decode, "serve.decode_tick"),
                         (flash, "serve.prefill_chunk")):
        (ev,) = [e for e in iteration["program"] if e[0] == span]
        assert all(ev[1] <= s and s + d <= ev[2] for _, s, d, _ in kernel)


def test_recorded_iteration_idle_by_engine_phase(iteration):
    paths = program.idle_paths(iteration, iteration["program"])
    total = sum(trace.idle_gaps(iteration).values())
    assert sum(paths.values()) == pytest.approx(total)
    # in this iteration every idle gap but a few tens of nanoseconds
    # lies under a phase of the step
    phases = sum(v for k, v in paths.items()
                 if k.startswith("bench.run_step > serve.step > serve."))
    assert total - phases < 1e-6


def test_recorded_iteration_readers(iteration):
    # the engine's spans as its tracer kept them, on a clock T0 seconds
    # ahead of the profiler's
    spans = [SpanRecord(name=n[len("serve."):], t0=T0 + a / 1e9,
                        dur=(b - a) / 1e9, attrs=attrs, sid=i + 1,
                        parent=None, tid=1)
             for i, (n, a, b, attrs) in enumerate(iteration["program"])]
    window = types.SimpleNamespace(origin=T0 - 1.0, closed=T0 + 1.0,
                                   traced=(T0 - 0.001, T0 + 1.0))
    run = types.SimpleNamespace(trace=iteration, traced=window.traced,
                                spans=spans, window=window)
    ops = program.first_chip(iteration)
    # one step: its length less the device's busy time in it
    (step,) = [e for e in iteration["program"] if e[0] == "serve.step"]
    idle_ns = step[2] - step[1] - program.busy_ns(ops, step[1], step[2])
    assert reader("engine_idle_ms_per_step").read(run) == \
        pytest.approx(idle_ns / 1e6, abs=0.01)
    (tick,) = [e for e in iteration["program"]
               if e[0] == "serve.decode_tick"]
    assert reader("decode_step_device_ms").read(run) == pytest.approx(
        program.busy_ns(ops, tick[1], tick[2]) / 1e6, abs=0.01)
    a = tick[3]
    assert reader("kv_live_share").read(run) == \
        pytest.approx(100 * a["ctx_tokens"] / (a["slots"] * a["pool_len"]))
