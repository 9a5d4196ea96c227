"""Drives the engine on the wall clock: sends every request itself
(``engine.submit``), steps the engine itself (``engine.step(now=...)``) and
stamps every committed token with the wall time at the end of the step
that committed it.

Set-up first runs every step program the window can call
(``harness.prewarm``), then the stream itself for a fixed number of decode
steps (``warmup.steps``, with every client started), so
that every seed reaches the window after the same number of planner steps.
Then the window opens and runs for ``seconds``; it closes at the end of the
first step that ends after that.  The traffic is a closed loop: each client
sends its next request as soon as its previous one finishes."""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from .prewarm import pow2

LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class CompileCounter:
    """Programs lowered (compiled, or loaded from the persistent cache),
    counted from JAX's monitoring events, and the seconds JAX spent on
    compile events."""

    def __init__(self):
        import jax
        self._mon = jax.monitoring
        self.count = 0
        self.seconds = 0.0

    def _on(self, event: str, secs: float, **_):
        if event.startswith("/jax/core/compile/"):
            self.seconds += secs
        if event == LOWERING_EVENT:
            self.count += 1

    def __enter__(self):
        self._mon.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        self._mon.unregister_event_duration_listener(self._on)


@dataclass
class ReqLog:
    rid: int
    prompt_len: int
    output_len: int
    due: float
    sent: float = 0.0
    admitted: List[float] = field(default_factory=list)   # each admission
    stamps: List[tuple] = field(default_factory=list)     # (t, n tokens)
    delivered: int = 0
    finished: Optional[float] = None
    served: Optional[List[int]] = None
    prompt: Optional[List[int]] = None

    @property
    def first_token(self) -> Optional[float]:
        return self.stamps[0][0] if self.stamps else None


@dataclass
class StepLog:
    t0: float
    t1: float
    kind: str
    batch: int
    gamma: int
    tokens: int
    admitted: int
    kv_blocks_used: int
    kv_blocks_total: int
    waiting: int
    draft_resident: bool
    width: int                  # longest block table, padded as the backend pads


@dataclass
class Window:
    open: float
    close: float
    steps: List[StepLog]
    requests: Dict[int, ReqLog]
    lowerings: int
    memory_events: List[tuple]
    backend_calls: List[tuple]  # (t, kind, detail) of traced backend calls


class Driver:
    def __init__(self, engine, stream, mix: dict, *, spans: bool = False,
                 first_id: int = 0):
        self.engine = engine
        self.first_id = first_id   # request ids: first_id + stream index
        self.stream = stream
        self.mix = mix
        self.t_origin = time.perf_counter()
        self.reqs: Dict[int, ReqLog] = {}
        self.next_index = 0
        self.clients_started = 0
        self.free_clients = 0
        self.steps: List[StepLog] = []
        self.seen_seq: Dict[int, object] = {}   # req -> its live Sequence
        self.preemptions = 0
        self.fin_index = 0
        self.backend_calls: List[tuple] = []
        self._annotate = None
        self.spans = spans

    # -- clock -----------------------------------------------------------
    def now(self) -> float:
        return time.perf_counter() - self.t_origin

    # -- spans and backend-call records (traced run only) ------------------
    def _wrap_layers(self):
        """Put spans around the engine's layers and record each backend
        call's sizes; done as the window opens, so that set-up runs the same
        code in a traced run as in an untraced one."""
        import jax
        ann = self._annotate = jax.profiler.TraceAnnotation
        be, mm = self.engine.backend, self.engine.memmgr
        calls = self.backend_calls

        def wrap(obj, attr, span, record=None):
            fn = getattr(obj, attr)

            def wrapped(*a, **kw):
                if record is not None:
                    calls.append((self.now(), attr, record(*a, **kw)))
                with ann(span):
                    return fn(*a, **kw)
            setattr(obj, attr, wrapped)

        def rec_prefill(seqs, *, with_draft):
            return {"prompts": [s.request.prompt_len for s in seqs],
                    "draft": bool(with_draft and be.draft_resident)}

        def rec_step(seqs, gamma):
            return {"ctx": [s.request.prompt_len + s.generated for s in seqs],
                    "gamma": int(gamma)}

        def rec_catchup(seqs):
            return {"rows": [(s.delta, s.request.prompt_len + s.generated)
                             for s in seqs if s.delta > 0]
                    if be.draft_resident else []}

        wrap(be, "prefill", "backend.prefill", rec_prefill)
        wrap(be, "step", "backend.step", rec_step)
        wrap(be, "draft_catchup", "backend.draft_catchup", rec_catchup)
        if mm is not None:
            for hook in ("offload_fn", "reload_fn", "migrate_fn", "grow_fn",
                         "shrink_fn"):
                if getattr(mm, hook, None) is not None:
                    wrap(mm, hook, f"memmgr.{hook[:-3]}")

    # -- sending ---------------------------------------------------------
    def _send(self, due: float) -> None:
        from repro.serving.request import Request
        i = self.next_index
        self.next_index += 1
        r = self.stream.request(i)
        toks = self.stream.tokens(i, r.prompt_len)
        rid = self.first_id + i
        log = ReqLog(rid, r.prompt_len, r.output_len, due, sent=self.now(),
                     prompt=toks)
        self.reqs[rid] = log
        self.engine.submit(Request(rid, due, r.prompt_len, r.output_len,
                                   prompt_tokens=toks))

    def _send_due(self) -> None:
        # clients start ``ramp_per_step`` at a time, so that the first
        # prefills are as small as the stream's later ones
        mix = self.mix
        k = min(mix["clients"] - self.clients_started, mix["ramp_per_step"])
        self.clients_started += k
        self.free_clients += k
        while self.free_clients:
            self.free_clients -= 1
            self._send(self.now())

    # -- bookkeeping after a step ---------------------------------------------
    def _observe(self, t_start: float, t_end: float, rep) -> None:
        eng = self.engine
        for s in eng.scheduler.running:
            log = self.reqs.get(s.req_id)
            if log is None:
                continue
            if self.seen_seq.get(s.req_id) is not s:
                if s.req_id in self.seen_seq:
                    self.preemptions += 1
                self.seen_seq[s.req_id] = s
                log.admitted.append(t_start)
            if s.generated > log.delivered:
                log.stamps.append((t_end, s.generated - log.delivered))
                log.delivered = s.generated
        done = eng.metrics.requests
        while self.fin_index < len(done):
            st = done[self.fin_index]
            self.fin_index += 1
            log = self.reqs.get(st.req_id)
            if log is None:
                continue
            if st.req_id not in self.seen_seq:
                log.admitted.append(t_start)
            self.seen_seq.pop(st.req_id, None)
            if st.tokens > log.delivered:
                log.stamps.append((t_end, st.tokens - log.delivered))
                log.delivered = st.tokens
            log.finished = t_end
            log.served = list(eng.backend.output_tokens(st.req_id)
                              [:st.tokens])
            self.free_clients += 1
        if rep is not None and rep.kind == "decode":
            bm = eng.scheduler.bm
            self.steps.append(StepLog(
                t_start, t_end, rep.kind, rep.batch, rep.gamma, rep.tokens,
                rep.admitted,
                sum(len(t) for t in bm.tables.values()), bm.total_blocks,
                eng.scheduler.num_waiting, bool(eng.backend.draft_resident),
                pow2(max((len(bm.tables.get(s.req_id, ()))
                          for s in eng.scheduler.running), default=1))))

    def step(self) -> None:
        self._send_due()
        t0 = self.now()
        if self._annotate is not None:
            with self._annotate("engine.step"):
                rep = self.engine.step(now=t0)
        else:
            rep = self.engine.step(now=t0)
        self._observe(t0, self.now(), rep)

    # -- phases ----------------------------------------------------------
    def warm_up(self, counter: CompileCounter, *, prewarm: bool = True) -> dict:
        """Set-up: every step program the window can call
        (``harness.prewarm``; skipped where an earlier stream in the same
        process ran them), then the stream until ``warmup.steps`` decode
        steps have run and every client has started."""
        from . import prewarm as pw
        t0, low0 = time.perf_counter(), counter.count
        calls = (pw.run(self.engine, self.mix["prewarm"],
                        self.stream.prompt_lengths,
                        self.mix["engine"]["gamma_max"]) if prewarm else {})
        out = {"prewarm_s": time.perf_counter() - t0,
               "prewarm_lowered": counter.count - low0, "prewarm_calls": calls}
        self.t_origin = time.perf_counter()
        want = self.mix["warmup"]["steps"]
        while (len(self.steps) < want
               or self.clients_started < self.mix["clients"]):
            self.step()
        out.update(warm_s=self.now(), steps=len(self.steps),
                   requests_sent=self.next_index)
        return out

    def window(self, seconds: float, counter: CompileCounter) -> Window:
        self.steps = []
        mm = self.engine.memmgr
        ev0 = len(mm.events) if mm is not None else 0
        low0 = counter.count
        if self.spans and self._annotate is None:
            self._wrap_layers()
        calls0 = len(self.backend_calls)
        t_open = self.now()
        while self.now() < t_open + seconds:
            self.step()
        t_close = self.now()
        evs = ([(e.kind, e.at, e.latency, dict(e.detail))
                for e in mm.events[ev0:]] if mm is not None else [])
        return Window(t_open, t_close, self.steps, self.reqs,
                      counter.count - low0, evs,
                      self.backend_calls[calls0:])

    def cancel_rest(self) -> int:
        """Withdraw every request still in the engine (after a window, so
        that another stream can start on an empty engine); returns how
        many."""
        ids = self.engine.inflight_req_ids()
        for rid in ids:
            self.engine.cancel_request(rid)
        return len(ids)
