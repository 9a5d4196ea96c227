"""The control of the served-token comparison, at a size a test run holds.

``control.py --rehearse`` serves a cell at reduced widths on the CPU for
three seeds and reads, over the same served sequences, the widest logit
gap of the program's tokens and of the tokens the reference computed in
int8 puts first, each through the decision a run makes.  The program's
reading must be correct, and the int8 control must read clearly wider:
otherwise the comparison could not tell a step down in precision from the
configuration's bfloat16.  (At the rehearsal's 2 layers of width 128 the
int8 error stays under the chip's limit; the chip readings at the cell's
own size, where the control comes out not correct, are in PERF.md.)  Run
by path:

  JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
CHECKOUT = CHIP.parents[1]
CELLS = [w["name"] for w in json.loads(
    (CHECKOUT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_int8_control_reads_wider_than_the_program(cell):
    p = subprocess.run(
        [sys.executable, str(CHIP / "control.py"), "--workload", cell,
         "--seeds", "11,12,13", "--seconds", "20",
         "--rehearse"], cwd=CHECKOUT, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=1200)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = [json.loads(x) for x in p.stdout.strip().splitlines()]
    rows, summary = lines[:-1], lines[-1]
    assert len(rows) == 3 and all(r["tokens"] > 0 for r in rows)
    gap = {who: [r[who]["checks"]["widest_logit_gap"]["value"] for r in rows]
           for who in ("program", "control")}
    for r in rows:
        for who in ("program", "control"):
            c = r[who]["checks"]
            assert c["widest_logit_gap"]["limit"] == summary["limit"]
            assert r[who]["correct"] == (
                c["widest_logit_gap"]["value"] <= summary["limit"]
                and c["tokens_compared"]["value"]
                >= c["tokens_compared"]["limit"])
    assert summary["program_correct"], rows
    assert max(gap["control"]) > 2 * max(gap["program"]), rows
