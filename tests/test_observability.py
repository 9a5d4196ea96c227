"""Flight recorder: deterministic traces, span balance, zero-cost no-op.

Pins the observability layer's three contracts:

* **determinism** — two same-seed runs emit byte-identical JSONL traces
  (the golden unit is the exported bytes, not a parsed comparison);
* **span balance** — every finished request's stage durations partition
  its end-to-end latency exactly (the stage machine closes each span as
  the next opens, so this holds by construction — the test pins it);
* **zero cost when off** — a run without a recorder (or with a disabled
  one) produces the same ``Metrics.summary()`` as the pre-recorder code
  path and records zero events.
"""
import json
import os
import sys
import time

import pytest

from repro import configs
from repro.serving.observability import (OUTCOMES, STAGES, MetricsRegistry,
                                         TraceRecorder)
from repro.serving.request import TIMELINE_RING_CAP, Metrics
from repro.serving.simulator import SimConfig, build_sim_cluster, \
    build_sim_engine
from repro.serving.workload import poisson_requests

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks.trace_report import (analyze, batch_bin,  # noqa: E402
                                     load_trace, restart_episodes,
                                     spec_surface, stage_waterfalls)
from repro.serving.costmodel import RTX_4090  # noqa: E402


def _cfg(**kw):
    return SimConfig(target=configs.get_config("paper-7b"),
                     draft=configs.get_draft_config("paper-7b"),
                     hw=RTX_4090, max_batch=256, seed=0, **kw)


def _cluster_run(trace=None, record_timeline=False):
    cl = build_sim_cluster(_cfg(), 2, "nightjar", router="jsq", trace=trace)
    m = cl.run(poisson_requests(20, 40, dataset="alpaca", seed=1),
               record_timeline=record_timeline)
    return m, cl


@pytest.fixture(scope="module")
def traced():
    rec = TraceRecorder()
    m, cl = _cluster_run(trace=rec)
    return rec, m, cl


# ---------------------------------------------------------------------------
# determinism
# ---------------------------------------------------------------------------


def test_trace_byte_identical_across_runs(traced):
    rec1, _, _ = traced
    rec2 = TraceRecorder()
    _cluster_run(trace=rec2)
    b1, b2 = rec1.jsonl_bytes(), rec2.jsonl_bytes()
    assert len(rec1.events) > 100
    assert rec1.dropped == 0
    assert b1 == b2


def test_trace_is_virtual_time_only(traced):
    """No wall-clock leaks: every timestamp is a finite non-negative
    virtual second well below any epoch-scale value."""
    rec, _, _ = traced
    for e in rec.events:
        assert 0.0 <= e["t"] < 1e6


# ---------------------------------------------------------------------------
# span balance
# ---------------------------------------------------------------------------


def test_span_balance_partitions_e2e(traced):
    """Every request with a terminal outcome: stage durations sum to the
    end-to-end latency within 1e-6, across all stages in STAGES only."""
    rec, m, _ = traced
    events = [json.loads(ln) for ln in rec.jsonl_lines()]
    wf = stage_waterfalls(events)
    assert wf, "no terminated requests in trace"
    fin = {rid: r for rid, r in wf.items() if r["outcome"] == "finished"}
    assert len(fin) >= 30
    for rid, r in wf.items():
        assert set(r["stages"]) <= set(STAGES)
        assert r["outcome"] in OUTCOMES
        total = sum(r["stages"].values())
        assert total == pytest.approx(r["e2e"], abs=1e-6), rid
    # open spans may only belong to requests without a terminal outcome
    for rid in rec.open_spans():
        assert rid not in rec.outcomes


def test_outcome_counts_match_metrics(traced):
    rec, m, _ = traced
    fin = sum(1 for o in rec.outcomes.values() if o == "finished")
    assert fin == sum(len(rm.latencies) for rm in m.per_replica)


# ---------------------------------------------------------------------------
# zero-cost no-op when disabled
# ---------------------------------------------------------------------------


def test_untraced_summary_identical_and_disabled_records_nothing(traced):
    _, m_traced, _ = traced
    m_plain, _ = _cluster_run()
    rec_off = TraceRecorder(enabled=False)
    m_off, _ = _cluster_run(trace=rec_off)
    # disabled recorder: zero events, zero registry traffic
    assert len(rec_off.events) == 0
    assert rec_off.registry._metrics == {}
    # untraced summaries are byte-identical (no spec section, same numbers)
    s_plain, s_off = m_plain.summary(), m_off.summary()
    assert json.dumps(s_plain, sort_keys=True) \
        == json.dumps(s_off, sort_keys=True)
    assert "spec" not in s_plain
    # a traced run adds ONLY the spec section on top of the same numbers
    s_traced = dict(m_traced.summary())
    assert "spec" in s_traced
    s_traced.pop("spec")
    assert json.dumps(s_plain, sort_keys=True) \
        == json.dumps(s_traced, sort_keys=True)


def test_spec_summary_section(traced):
    _, m, _ = traced
    spec = m.summary()["spec"]
    assert spec["steps"] > 0
    assert 0.0 <= spec["spec_step_fraction"] <= 1.0
    assert spec["spec_off_step_fraction"] == pytest.approx(
        1.0 - spec["spec_step_fraction"], abs=1e-9)
    for g, row in spec["per_gamma"].items():
        assert row["steps"] > 0
        if int(g) > 0 and "acceptance_rate" in row:
            assert 0.0 <= row["acceptance_rate"] <= 1.0


# ---------------------------------------------------------------------------
# exporters + analyzer round-trip
# ---------------------------------------------------------------------------


def test_jsonl_roundtrip_through_report(traced, tmp_path):
    rec, m, _ = traced
    p = str(tmp_path / "trace.jsonl")
    rec.export_jsonl(p)
    events = load_trace(p)
    assert len(events) == len(rec.events)
    report = analyze(events)
    fin = sum(1 for o in rec.outcomes.values() if o == "finished")
    assert report["waterfall"]["outcomes"]["finished"] == fin
    assert report["spec_surface"], "no engine step spans in report"
    # engine step spans carry the planner tuple
    steps = [e for e in events
             if e["cat"] == "engine" and e["name"] == "step"]
    assert steps and all(
        {"B", "gamma", "tokens", "accepted"} <= set(e["args"]) for e in steps)


def test_chrome_export(traced, tmp_path):
    rec, _, _ = traced
    p = str(tmp_path / "trace.json")
    rec.export_chrome(p)
    with open(p, "r", encoding="utf-8") as f:
        payload = json.load(f)
    evs = payload["traceEvents"]
    procs = {e["args"]["name"] for e in evs
             if e.get("ph") == "M" and e["name"] == "process_name"}
    assert {"replica 0", "replica 1"} <= procs
    assert any(e.get("ph") == "X" and e["cat"] == "request" for e in evs)


def test_unknown_format_raises(traced, tmp_path):
    rec, _, _ = traced
    with pytest.raises(ValueError):
        rec.export(str(tmp_path / "x"), fmt="protobuf")


# ---------------------------------------------------------------------------
# analyzer units
# ---------------------------------------------------------------------------


def test_batch_bin_powers_of_two():
    assert [batch_bin(b) for b in (1, 2, 3, 4, 5, 8, 9, 256)] \
        == [1, 2, 4, 4, 8, 8, 16, 256]


def test_restart_episode_detection_synthetic():
    """Hand-built trace: enter spec_off at t=1, reload at t=2, resume at
    t=3, AR step, then the first speculative commit at t=4 closes the
    episode at cost 3.5s."""
    evs = [
        {"ph": "i", "cat": "fleet", "name": "brownout", "t": 1.0, "pid": -1,
         "args": {"from": "normal", "to": "spec_off"}},
        {"ph": "X", "cat": "engine", "name": "step", "t": 1.5, "dur": 0.1,
         "pid": 0, "args": {"B": 4, "gamma": 0, "tokens": 4, "accepted": 0,
                            "prefill_tokens": 0}},
        {"ph": "i", "cat": "memmgr", "name": "reload", "t": 2.0, "pid": 0,
         "args": {}},
        {"ph": "i", "cat": "fleet", "name": "brownout", "t": 3.0, "pid": -1,
         "args": {"from": "spec_off", "to": "normal"}},
        {"ph": "X", "cat": "engine", "name": "step", "t": 3.2, "dur": 0.1,
         "pid": 0, "args": {"B": 4, "gamma": 0, "tokens": 4, "accepted": 0,
                            "prefill_tokens": 0}},
        {"ph": "X", "cat": "engine", "name": "step", "t": 4.0, "dur": 0.5,
         "pid": 0, "args": {"B": 4, "gamma": 2, "tokens": 9, "accepted": 5,
                            "prefill_tokens": 0}},
    ]
    eps = restart_episodes(evs)
    assert len(eps) == 1
    ep = eps[0]
    assert ep["reloads"] == 1
    assert ep["deepest_stage"] == "spec_off"
    assert ep["restart_cost_s"] == pytest.approx(3.5)
    assert ep["spec_off_s"] == pytest.approx(2.0)
    assert ep["recovery_s"] == pytest.approx(1.5)
    # the surface only sees the three step spans
    surf = spec_surface(evs)
    assert surf["4/2"]["acceptance_rate"] == pytest.approx(5 / 8)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_registry_exposition_deterministic():
    def build():
        reg = MetricsRegistry()
        reg.counter("a_total", "a help").inc(3)
        reg.gauge("g").set(2.5)
        h = reg.histogram("lat_seconds", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        h.observe(5.0)
        return reg
    e1, e2 = build().exposition(), build().exposition()
    assert e1 == e2
    assert "# TYPE a_total counter" in e1
    assert 'lat_seconds_bucket{le="+Inf"} 3' in e1
    assert "lat_seconds_count 3" in e1


def test_registry_type_conflict_raises():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(ValueError):
        reg.gauge("x")


# ---------------------------------------------------------------------------
# clocks: virtual (default) and wall
# ---------------------------------------------------------------------------


def test_wall_mode_stamps_the_recorders_monotonic_clock():
    """The engine's ``t`` is ignored: stamps come from the recorder's own
    clock, never decrease, and measure the wall time between hooks."""
    rec = TraceRecorder(wall=True)
    rec.req_submit(1, 1e6, 0)
    time.sleep(0.01)
    rec.req_stage(1, -5.0, "decode", 0)
    rec.instant("engine", "mark", 42.0)
    ts = [e["t"] for e in rec.events]
    assert ts == sorted(ts) and 0.0 <= ts[0] < 1.0
    queue = next(e for e in rec.events if e["name"] == "queue")
    assert queue["dur"] >= 0.01
    with rec.span("nightjar.admit", waiting=3) as sp:
        sp.set(admitted=2)
    ev = rec.events[-1]
    assert (ev["name"], ev["args"]) == ("nightjar.admit",
                                        {"admitted": 2, "waiting": 3})
    assert ev["t"] >= ts[-1] and ev["dur"] >= 0.0


def test_virtual_mode_stamps_the_engine_time_and_opens_no_host_spans():
    rec = TraceRecorder()
    rec.req_submit(1, 2.5, 0)
    rec.req_stage(1, 4.0, "decode", 0)
    rec.step_span(4.0, 4.5, 0, batch=2, gamma=1, tokens=3, accepted=1)
    assert [e["t"] for e in rec.events] == [2.5, 2.5, 4.0]
    assert rec.events[-1]["dur"] == 0.5
    eng = build_sim_engine(_cfg(), "nightjar", trace=rec)
    assert eng.trace is rec and eng.spans is None
    assert not any(e["name"].startswith("nightjar.") for e in rec.events)


# ---------------------------------------------------------------------------
# bounded timeline ring (satellite: unbounded-growth fix)
# ---------------------------------------------------------------------------


def test_timeline_ring_bounded():
    m = Metrics()
    m.use_timeline_ring(cap=8)
    for i in range(20):
        m.timeline.append({"t": float(i)})
    assert len(m.timeline) == 8
    assert m.timeline[0]["t"] == 12.0
    assert TIMELINE_RING_CAP >= 4096


def test_engine_default_records_no_timeline():
    eng = build_sim_engine(_cfg(), "nightjar")
    m = eng.run(poisson_requests(20, 10, dataset="alpaca", seed=1))
    assert m.timeline == [] or len(m.timeline) == 0
    assert "spec" not in m.summary()


def test_engine_recorder_ring_eviction():
    """A tiny-capacity recorder keeps memory bounded and counts drops."""
    rec = TraceRecorder(capacity=64)
    eng = build_sim_engine(_cfg(), "nightjar", trace=rec)
    eng.run(poisson_requests(20, 20, dataset="alpaca", seed=1))
    assert len(rec.events) == 64
    assert rec.dropped > 0
