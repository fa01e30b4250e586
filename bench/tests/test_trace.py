"""The trace reduction: idle share, kernel time by output shape, idle
gaps by what the host was doing, on a hand-built trace in the form
``harness.trace.extract`` returns, and on 82 ms recorded on a v5e
(``data/trace_v5e_82ms.json``)."""

import json
from pathlib import Path

import pytest

from harness import trace

MS = 1_000_000
TPU = ' custom-call(...), custom_call_target="tpu_custom_call"'
RECORDED = Path(__file__).parent / "data" / "trace_v5e_82ms.json"


def hand_built():
    # host spans cover [0, 100 ms]; the device runs 10-30 and 20-50 ms
    # (overlapping: 40 ms busy) and a kernel at 60-70 ms
    return {
        "devices": {"/device:TPU:0": [
            ["fusion.1", 10 * MS, 20 * MS, ""],
            ["fusion.2", 20 * MS, 30 * MS, ""],
            ["%vmap_vmap.3 = bf16[3,3,256,64]" + TPU, 60 * MS, 10 * MS,
             ""],
        ]},
        "host": [["bench.run_step", 0, 55 * MS],
                 ["bench.sleep", 55 * MS, 58 * MS],
                 ["bench.run_step", 58 * MS, 100 * MS]],
    }


def test_busy_and_idle_share():
    ex = hand_built()
    assert trace.window_of(ex) == (0, 100 * MS)
    assert trace.busy_s(ex) == pytest.approx(0.050)


def test_kernel_time_by_name_or_detail():
    # the output shape is read from the operation's name (its HLO text)
    secs, n, names = trace.kernel_seconds(hand_built(),
                                          lambda d: d[:2] == (3, 3))
    assert (secs, n, names) == (pytest.approx(0.010), 1, ["%vmap_vmap.3"])
    assert trace.kernel_seconds(hand_built(), lambda d: False)[1] == 0


def test_pallas_output_reads_only_tpu_kernels():
    assert trace.pallas_output("%c.1 = bf16[64,3,3,64]{3,2,1,0} " + TPU) \
        == (64, 3, 3, 64)
    assert trace.pallas_output("%fusion.2 = bf16[64,3]{1,0} fusion()") \
        is None
    assert trace.pallas_output('%custom-call.4 = bf16[2,2]{1,0} '
                               'custom-call(), custom_call_target='
                               '"AllocateBuffer"') is None


@pytest.fixture(scope="module")
def recorded():
    return json.loads(RECORDED.read_text())


def test_recorded_trace_kernels(recorded):
    # smollm-135m: 3 kv heads of 3 query heads each, head_dim 64, 64 slots
    ops = recorded["devices"]["/device:TPU:0"]
    decode, nd, names = trace.kernel_seconds(recorded,
                                             lambda d: d == (64, 3, 3, 64))
    assert nd == 2 and names == ["%closed_call.17"]
    by_hand = sum(d for name, _, d, _ in ops
                  if name.startswith("%closed_call.17 ")) / 1e9
    assert decode == pytest.approx(by_hand)
    assert decode == pytest.approx(0.014738378)
    flash, nf, _ = trace.kernel_seconds(
        recorded, lambda d: len(d) == 4 and d[:2] == (3, 3) and d[3] == 64)
    assert nf == 7
    assert 0 < flash < decode


def test_recorded_trace_busy_and_gaps(recorded):
    lo, hi = trace.window_of(recorded)
    assert (lo, hi) == (0, 82 * MS)
    busy = trace.busy_s(recorded)
    assert 0 < busy < 0.082
    gaps = trace.idle_gaps(recorded)
    assert list(gaps) == ["bench.run_step"]
    assert busy + gaps["bench.run_step"] == pytest.approx(0.082)


def test_idle_gaps_by_host_activity():
    gaps = trace.idle_gaps(hand_built())
    # idle: 0-10 (run_step), 50-60 (mid 55: the sleep starts there),
    # 70-100 (run_step)
    assert gaps["bench.run_step"] == pytest.approx(0.040)
    assert gaps["bench.sleep"] == pytest.approx(0.010)


def test_op_seconds_clip_to_the_window():
    ex = hand_built()
    ops = trace.op_seconds(ex, (15 * MS, 100 * MS))
    assert ops["fusion.1"] == pytest.approx(0.015)


def test_merge():
    assert trace.merge([(5, 7), (1, 3), (2, 4), (7, 8)]) == [[1, 4], [5, 8]]
