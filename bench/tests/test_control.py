"""The control: the plain reference put in the program's place and
computed in float8, the precision below the configurations' bfloat16,
has to fail the comparison that decides ``correct``, while the program
passes it.  At a size a test run holds (the CPU rehearsal's tiny
widths, against the limit set from readings at those widths), on three
seeds; full-size readings are taken on the chip with
``bench/tools/control.py`` (see PERF.md)."""

import json
import os
import subprocess
import sys

import pytest

from harness.spec import BENCH

CELL = "smollm-135m.chat"


@pytest.fixture(scope="module")
def readings():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(BENCH / "tools" / "control.py"),
         "--workload", CELL, "--seeds", "5,6,7", "--seconds", "2",
         "--rehearse"], env=env, capture_output=True, text=True,
        timeout=900, check=True).stdout
    return [json.loads(line) for line in out.splitlines()
            if line.startswith("{")]


def test_control_fails_and_program_passes(readings):
    limit = json.loads((BENCH / "limits" / f"{CELL}.json").read_text())[
        "rehearsal"]["max_logit_gap"]["limit"]
    assert len(readings) == 3
    for r in readings:
        assert r["tokens"] > 0 and r["failed"] == 0
        assert r["program_gap"] <= limit, r
        assert r["control_gap"] > limit, r
