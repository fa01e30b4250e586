"""Traffic repeats exactly from a seed, every seed gets the same set of
requests, and a new mix or cell is picked up by adding files alone."""

import json
import shutil

import numpy as np
import pytest

from harness import spec
from harness.spec import BENCH, ROOT

#: a mix of another shape than chat's: uniform prompts, fixed outputs
UNIFORM = {"generator": "mix", "loop": "open", "rate_per_s": 7.0,
           "prompt": {"dist": "uniform", "min": 256, "max": 3072},
           "output": {"dist": "uniform", "min": 16, "max": 64}}
MIXES = ("chat", "uniform")


def mix(name):
    if name == "uniform":
        return dict(UNIFORM)
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


@pytest.fixture(scope="module")
def gen():
    return spec.load_module(spec.traffic_module_path("mix"))


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(gen, name):
    a = gen.schedule(mix(name), 2**31 + 17, 10.0, 49152)
    b = gen.schedule(mix(name), 2**31 + 17, 10.0, 49152)
    assert [(r.prompt, r.max_new, r.due) for r in a.requests] == \
        [(r.prompt, r.max_new, r.due) for r in b.requests]


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_requests(gen, name):
    a = gen.schedule(mix(name), 1, 10.0, 1000)
    b = gen.schedule(mix(name), 2, 10.0, 1000)
    assert a.pairs() == b.pairs()                 # in the same order
    assert len(set(a.pairs())) > 1
    assert [r.prompt for r in a.requests] != [r.prompt for r in b.requests]
    assert [r.due for r in a.requests] == [r.due for r in b.requests]
    lens = [len(r.prompt) for r in a.requests]
    assert lens != sorted(lens)                   # not sorted by length
    gaps = np.diff([0.0] + [r.due for r in a.requests])
    assert not np.allclose(gaps, sorted(gaps))    # nor by gap
    assert a.requests[-1].due < 10.0


def test_open_loop_rate_and_clamps(gen):
    m = mix("chat")
    s = gen.schedule(m, 3, 20.0, 100)
    assert len(s.requests) == round(m["rate_per_s"] * 20.0)
    dues = [r.due for r in s.requests]
    assert dues == sorted(dues)
    for p, o in s.pairs():
        assert m["prompt"]["min"] <= p <= m["prompt"]["max"]
        assert m["output"]["min"] <= o <= m["output"]["max"]
    med = np.median([p for p, _ in s.pairs()])
    assert abs(med - m["prompt"]["median"]) <= 0.05 * m["prompt"]["median"]


def test_a_new_mix_and_cell_are_picked_up_from_new_files(tmp_path):
    """A later PR adds a traffic file and a workload entry; no file that
    exists is edited, and the harness finds both by name."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"].append({
        "name": "smollm-135m.burst", "config": "smollm-135m",
        "traffic": "burst", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    new_mix = dict(mix("chat"), rate_per_s=3.0)
    new_mix["prompt"] = {"dist": "uniform", "min": 8, "max": 16}
    (root / "bench" / "traffic" / "burst.json").write_text(
        json.dumps(new_mix))
    (root / "bench" / "limits" / "smollm-135m.burst.json").write_text(
        (BENCH / "limits" / "smollm-135m.chat.json").read_text())
    cell = spec.load_cell("smollm-135m.burst", root)
    assert cell.traffic["rate_per_s"] == 3.0
    assert {m["name"] for m in cell.end_to_end} >= {"itl_p99_ms", "setup_s"}
    gen = spec.load_module(
        spec.traffic_module_path(cell.traffic["generator"], root))
    s = gen.schedule(cell.traffic, 5, 4.0, 100)
    assert len(s.requests) == 12
    assert all(8 <= p <= 16 for p, _ in s.pairs())
    for name in ("BENCHMARK.json",):
        assert (ROOT / name).read_text() != (root / name).read_text()
    for p in (BENCH / "traffic").glob("*.json"):
        assert (root / "bench" / "traffic" / p.name).read_text() == \
            p.read_text()
