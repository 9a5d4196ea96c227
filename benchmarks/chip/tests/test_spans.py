"""The readers of the program's ``nightjar.*`` spans, on a small synthetic
trace and on one recorded on the CPU.  Run by path:

  JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests/test_spans.py
"""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(CHIP.parents[1] / "src"))

from harness import readers, spans as sp  # noqa: E402
from harness.trace import busy_seconds  # noqa: E402

NEW = ("host_step_share.saturated", "idle_in_device_wait_share.saturated",
       "idle_outside_program_share.saturated",
       "kv_pages_valid_share.saturated")

# window [0, 10] s; a step straddles its end and is clipped there
T0, T1 = 0.0, 10.0
OPS = [("fusion.1", 1.5, 2.0), ("paged_attention.3", 4.2, 1.3),
       ("fusion.2", 7.2, 1.0), ("copy.1", -1.0, 1.2)]
SPANS = [
    ("nightjar.step", 1.0, 4.0, {"batch": 2}),
    ("nightjar.decode", 1.2, 3.5, {"pages_valid": 3, "pages_walked": 8}),
    ("nightjar.device_wait", 1.4, 2.4, {"program": "decode"}),
    ("nightjar.step", 6.0, 5.0, {"batch": 2}),
    ("nightjar.decode", 7.0, 2.0, {"pages_valid": 5, "pages_walked": 8}),
    ("nightjar.device_wait", 7.1, 1.3, {"program": "spec"}),
    ("nightjar.admit", 11.0, 0.5, {}),
]


def _ctx():
    ops = [(n, max(s, T0), min(s + d, T1) - max(s, T0)) for n, s, d in OPS
           if s + d > T0 and s < T1]
    return SimpleNamespace(trace={"ops": ops, "t0": T0, "t1": T1,
                                  "nightjar": sp.clip_spans(SPANS, T0, T1)})


def test_interval_arithmetic():
    a = [(0.0, 2.0), (3.0, 6.0)]
    b = [(1.0, 4.0), (5.0, 5.5)]
    assert sp.intersect(a, b) == [(1.0, 2.0), (3.0, 4.0), (5.0, 5.5)]
    assert sp.subtract(a, b) == [(0.0, 1.0), (4.0, 5.0), (5.5, 6.0)]
    assert sp.subtract(a, []) == a and sp.intersect(a, []) == []


def test_the_window_edge_clips_spans():
    clipped = sp.clip_spans(SPANS, T0, T1)
    assert [s[0] for s in clipped].count("nightjar.admit") == 0
    last_step = [s for s in clipped if s[0] == "nightjar.step"][-1]
    assert last_step[1:3] == (6.0, 4.0) and last_step[3] == {"batch": 2}


def test_the_three_idle_parts_partition_the_device_idle():
    ctx = _ctx()
    tr = ctx.trace
    parts = sp.idle_parts(tr["ops"], tr["nightjar"], T0, T1)
    idle = (T1 - T0) - busy_seconds(tr["ops"])
    assert sum(parts.values()) == pytest.approx(idle, abs=1e-12)
    # device busy 0-0.2, 1.5-3.5, 4.2-5.5, 7.2-8.2; steps 1-5, 6-10;
    # waits 1.4-3.8, 7.1-8.4
    assert parts["in_device_wait"] == pytest.approx(0.1 + 0.3 + 0.1 + 0.2)
    assert parts["outside_step"] == pytest.approx(0.8 + 0.5)
    assert parts["in_step_host"] == pytest.approx(0.4 + 0.4 + 1.1 + 1.6)


@pytest.mark.parametrize("name,want", [
    (NEW[0], 100 * (8.0 - 3.7) / 10),
    (NEW[1], 100 * 0.7 / 10),
    (NEW[2], 100 * 1.3 / 10),
    (NEW[3], 100 * 8 / 16),
])
def test_readers(name, want):
    assert readers.load_reader(name)(_ctx()) == pytest.approx(want)


@pytest.mark.parametrize("name", NEW)
def test_readers_find_nothing_without_the_programs_spans(name):
    read = readers.load_reader(name)
    assert read(SimpleNamespace(trace=None)) is None
    bare = _ctx()
    del bare.trace["nightjar"]
    assert read(bare) is None


def test_read_spans_from_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    from repro.serving.observability import TraceRecorder
    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    rec = TraceRecorder(wall=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        rec.mark_clock()
        with rec.engine_step() as step:
            with rec.span("nightjar.device_wait", program="decode"):
                f(x).block_until_ready()
            step.set(batch=1)
    finally:
        jax.profiler.stop_trace()
    got = sp.read_spans(str(tmp_path))
    assert [s[0] for s in got] == ["nightjar.clock", "nightjar.step",
                                   "nightjar.device_wait"]
    assert got[1][3]["batch"] == 1 and got[1][3]["step_num"] == 1
    assert got[2][3]["program"] == "decode"
    assert sp.host_step_seconds(got) == pytest.approx(
        got[1][2] - got[2][2], abs=1e-9)
