"""Flight recorder in wall mode on the real tier.

One reduced real engine serves the same prompts twice: first with no
recorder, then with a wall-mode recorder attached under a CPU
``jax.profiler`` trace.  The tests read both sinks of the second run (the
recorder's ring buffer and the profiler's ``.xplane.pb``) and pin:

* the nesting: every ``nightjar.device_wait`` lies inside a backend span,
  which lies inside a ``nightjar.step``;
* the step spans' arguments equal each ``StepReport``;
* the ring buffer and the profiler hold the same span names and counts,
  and their starts agree within 1 ms once shifted by ``nightjar.clock``;
* the registry counts what the spans carry;
* detached, the token streams are those of the traced run;
* the step programs name their phases (``jax.named_scope``), so a device
  op's metadata says whether it drafts, verifies, decodes or extends.
"""
import dataclasses
import glob
import os
import re
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.spec_decode import make_paged_ar_step, make_paged_spec_step
from repro.serving.observability import TraceRecorder
from repro.serving.real_backend import build_real_engine
from repro.serving.workload import tiny_requests

BACKEND_SPANS = ("nightjar.prefill", "nightjar.catchup", "nightjar.decode")


def _serve(engine, reqs):
    for r in reqs:
        engine.submit(r)
    reports = []
    while (rep := engine.step()) is not None:
        reports.append(rep)
    return reports


def _xplane(trace_dir):
    """(name, start s, duration s, stats) of every ``nightjar.*`` host
    event, in start order."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("nightjar."):
                    out.append((ev.name, ev.start_ns * 1e-9,
                                ev.duration_ns * 1e-9, dict(ev.stats)))
    return sorted(out, key=lambda e: e[1])


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    engine = build_real_engine("paper-7b", "fixed-2", reduced=True,
                               max_batch=4, gamma_max=3)
    reqs = tiny_requests(6, rate_qps=1e6, prompt_len=12, output_len=8,
                         vocab=engine.backend.target.cfg.vocab_size, seed=3)
    _serve(engine, reqs)
    plain = {r.req_id: list(engine.backend.output_tokens(r.req_id))
             for r in reqs}
    again = [dataclasses.replace(r, req_id=r.req_id + 100) for r in reqs]
    rec = TraceRecorder(wall=True)
    trace_dir = str(tmp_path_factory.mktemp("xplane"))
    jax.profiler.start_trace(trace_dir)
    try:
        engine.attach_trace(rec)
        reports = _serve(engine, again)
    finally:
        jax.profiler.stop_trace()
    traced = {r.req_id - 100: list(engine.backend.output_tokens(r.req_id))
              for r in again}
    return dict(plain=plain, traced=traced, reports=reports, rec=rec,
                xplane=_xplane(trace_dir), engine=engine)


def _inside(inner, outer, eps=1e-9):
    return (outer[1] - eps <= inner[1]
            and inner[1] + inner[2] <= outer[1] + outer[2] + eps)


def test_device_waits_nest_in_backend_spans_in_steps(served):
    ev = served["xplane"]
    steps = [e for e in ev if e[0] == "nightjar.step"]
    backend = [e for e in ev if e[0] in BACKEND_SPANS]
    waits = [e for e in ev if e[0] == "nightjar.device_wait"]
    assert steps and backend and waits
    assert {e[3]["program"] for e in waits} >= {"extend_target", "spec"}
    for w in waits:
        assert any(_inside(w, b) for b in backend), w
    for b in backend:
        assert any(_inside(b, s) for s in steps), b


def test_step_span_arguments_equal_step_reports(served):
    steps = [e[3] for e in served["xplane"] if e[0] == "nightjar.step"]
    reports = served["reports"]
    # the last step found nothing to do and carries no report
    assert len(steps) == len(reports) + 1
    assert [s["step_num"] for s in steps] == list(range(1, len(steps) + 1))
    for st, rep in zip(steps, reports):
        assert (st["batch"], st["gamma"], st["tokens"], st["admitted"]) == \
            (rep.batch, rep.gamma, rep.tokens, rep.admitted)
    assert any(r.gamma == 2 for r in reports)
    ring = [e["args"] for e in served["rec"].events
            if e["name"] == "nightjar.step"]
    assert ring == [{k: v for k, v in st.items()
                     if k not in ("step_num", "_r")} for st in steps]


def test_ring_and_profiler_hold_the_same_spans(served):
    ev = served["xplane"]
    ring = [e for e in served["rec"].events
            if e["name"].startswith("nightjar.")]
    assert Counter(e["name"] for e in ring) == Counter(e[0] for e in ev)
    mark_x = next(e for e in ev if e[0] == "nightjar.clock")
    mark_r = next(e for e in ring if e["name"] == "nightjar.clock")
    assert mark_x[3]["t"] == pytest.approx(mark_r["t"], abs=1e-9)
    shift = mark_x[1] - mark_x[3]["t"]
    for name in {e[0] for e in ev}:
        xs = [e[1] for e in ev if e[0] == name]
        rs = sorted(e["t"] + shift for e in ring if e["name"] == name)
        np.testing.assert_allclose(rs, xs, rtol=0, atol=1e-3, err_msg=name)


def test_registry_counts_what_the_spans_carry(served):
    ring, reg = served["rec"].events, served["rec"].registry
    decode = [e["args"] for e in ring if e["name"] == "nightjar.decode"]
    assert decode
    for k in ("pages_valid", "pages_walked"):
        assert reg.counter(f"kv_{k}_total").value == sum(a[k] for a in decode)
    assert all(0 < a["pages_valid"] <= a["pages_walked"]
               == a["padded_rows"] * a["width"] for a in decode)
    waits = sum(e["dur"] for e in ring if e["name"] == "nightjar.device_wait")
    assert reg.counter("device_wait_seconds_total").value == \
        pytest.approx(waits, abs=1e-6)
    assert reg.counter("prefill_tokens_total").value == 6 * 12
    assert reg.counter("preemptions_total").value == 0


def test_detached_token_streams_are_the_traced_ones(served):
    assert served["plain"] == served["traced"]
    assert all(len(t) >= 8 for t in served["plain"].values())


def test_step_programs_name_their_phases(served):
    """Each step program's ops carry their phase in their op names."""
    be = served["engine"].backend
    t, d = be.target, be.draft
    shape = jax.eval_shape
    key = shape(lambda: jax.random.PRNGKey(0))
    tp, dp = shape(lambda: be.tparams), shape(lambda: be.dparams)
    tpg, dpg = shape(lambda: be.tkv.pages), shape(lambda: be.dkv.pages)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)   # noqa: E731
    tables, rows, toks = i32(2, 4), i32(2), i32(2, 3)

    def scopes(lowered, fn):
        return set(re.findall(rf"jit\({fn}\)/(\w+)/",
                              lowered.as_text(debug_info=True)))

    assert scopes(jax.jit(make_paged_ar_step(t)).lower(
        key, tp, tpg, tables, rows, rows), "ar_step") == {"decode"}
    assert scopes(jax.jit(make_paged_spec_step(t, d),
                          static_argnames=("gamma",)).lower(
        key, tp, dp, tpg, dpg, tables, rows, rows, gamma=2),
        "spec_step") == {"draft", "verify"}
    assert scopes(be._extend_t.lower(key, tp, tpg, toks, tables, rows, rows),
                  "_extend_target") == {"extend"}
    assert scopes(be._extend_d.lower(dp, dpg, toks, tables, rows, rows),
                  "_extend_draft") == {"extend"}
