"""A whole run on the CPU at tiny widths (``--rehearse``: no chip
check, no device metric), with the timed path broken underneath the
harness; ``correct`` has to come out false for each fault a serving
cell can have.  An unbroken run is the control: it comes out true.

Faults are planted in the engine's jitted decode step after the
harness built the engine:

* ``stale_state``: the step returns the cache it was given, so no
  token's key and value is ever appended;
* ``altered_token``: the step's logits are rolled by one along the
  vocabulary, so the token it produces is not the one it computed;
* ``half_batch``: the second half of the batch's rows is left out, each
  of those rows taking the logits of a row of the first half.

(One chip: there is no exchange between chips to leave out.)

The cell's own rate (under 1 req/s) would bring two requests into a
2-s window, and at tiny widths each is done before the next comes, so
the second half of the batch would never hold a request; the run is
offered 20 req/s instead, which keeps every slot busy.
"""

import dataclasses
import json

import pytest

import run as bench_run
from harness import spec

CELL = "smollm-135m.chat"
#: requests per second offered in these runs
RATE = 20.0


def _run(monkeypatch, capsys, fault):
    build = bench_run.build
    load_cell = spec.load_cell

    def busy_cell(*a, **kw):
        cell = load_cell(*a, **kw)
        return dataclasses.replace(
            cell, traffic=dict(cell.traffic, rate_per_s=RATE))

    monkeypatch.setattr(spec, "load_cell", busy_cell)

    def broken_build(*a, **kw):
        cfg, params, engine = build(*a, **kw)
        step = engine._decode

        def decode(params, cache, tokens, **kw):
            logits, new_cache = step(params, cache, tokens, **kw)
            if fault == "stale_state":
                return logits, cache
            if fault == "altered_token":
                import jax.numpy as jnp
                return jnp.roll(logits, 1, axis=-1), new_cache
            if fault == "half_batch":
                half = logits.shape[0] // 2
                return logits.at[half:2 * half].set(logits[:half]), new_cache
            return logits, new_cache

        engine._decode = decode
        return cfg, params, engine

    monkeypatch.setattr(bench_run, "build", broken_build)
    rc = bench_run.main(["--workload", CELL, "--seed", "2024",
                         "--seconds", "2", "--rehearse"])
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


@pytest.mark.parametrize("fault", ["stale_state", "altered_token",
                                   "half_batch"])
def test_fault_is_not_correct(monkeypatch, capsys, fault):
    res = _run(monkeypatch, capsys, fault)
    assert res["attempted"] > 0
    assert res["correct"] is False, res["checks"]


def test_unbroken_run_is_correct(monkeypatch, capsys):
    res = _run(monkeypatch, capsys, None)
    assert res["correct"] is True, res["checks"]
    assert "metrics" not in res            # a rehearsal names no device metric
    assert list(res)[-1] == "checks"
