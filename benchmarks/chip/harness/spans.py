"""The program's own host spans in the profiler trace, and their reduction
to numbers.

A wall-mode flight recorder (``repro.serving.observability``, attached with
``engine.attach_trace(TraceRecorder(wall=True))``) writes ``nightjar.*``
spans into the profiler's trace, on the profiler's clock, with each span's
arguments as the event's stats.  ``read_spans`` reads them out of the same
``.xplane.pb`` that ``trace.read_xplane`` reads; everything after that is
interval arithmetic on (name, start s, duration s, stats) tuples.

The device's idle time in a window splits into three disjoint parts:
inside a ``nightjar.device_wait`` (dispatch, operand transfer, launch,
readback latency), with no ``nightjar.step`` open (the client, the
profiler), and under a step's own host work (the rest of the steps)."""
from __future__ import annotations

import glob
import os
from typing import Iterable, List, Tuple

from .trace import union

Span = Tuple[str, float, float, dict]   # (name, start s, duration s, stats)
Interval = Tuple[float, float]

PREFIX = "nightjar."


def read_spans(trace_dir: str) -> List[Span]:
    """Every ``nightjar.*`` host event of the newest trace under
    ``trace_dir``, with its stats, in start order."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out: List[Span] = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    out.append((ev.name, ev.start_ns * 1e-9,
                                ev.duration_ns * 1e-9, dict(ev.stats)))
    return sorted(out, key=lambda s: s[1])


def clip_spans(spans: Iterable[Span], t0: float, t1: float) -> List[Span]:
    """Spans cut to [t0, t1], stats kept; those wholly outside dropped."""
    out = []
    for name, s, d, stats in spans:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((name, a, b - a, stats))
    return out


def covered(spans: Iterable[Span], name: str) -> List[Interval]:
    """Disjoint intervals covered by the spans called ``name``."""
    return union((n, s, d) for n, s, d, _ in spans if n == name)


def intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """``a`` minus ``b``, both sorted lists of disjoint intervals."""
    out, j = [], 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k, cur = j, lo
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def length(iv: Iterable[Interval]) -> float:
    return sum(b - a for a, b in iv)


def idle_parts(ops, spans: List[Span], t0: float, t1: float) -> dict:
    """Seconds of [t0, t1] with no device operation, in three parts:
    ``in_device_wait`` (idle inside a step's ``nightjar.device_wait``),
    ``outside_step`` (idle with no ``nightjar.step`` open) and
    ``in_step_host`` (idle during the rest of a step).  They sum to the
    window's idle seconds."""
    idle = subtract([(t0, t1)], union(ops))
    step = covered(spans, "nightjar.step")
    in_step = intersect(idle, step)
    wait = intersect(in_step, covered(spans, "nightjar.device_wait"))
    return {"in_device_wait": length(wait),
            "outside_step": length(subtract(idle, step)),
            "in_step_host": length(subtract(in_step, wait))}


def host_step_seconds(spans: List[Span]) -> float:
    """Seconds inside a ``nightjar.step`` and outside every
    ``nightjar.device_wait``: the steps' host work."""
    return length(subtract(covered(spans, "nightjar.step"),
                           covered(spans, "nightjar.device_wait")))


def pages(spans: Iterable[Span]) -> Tuple[int, int]:
    """(pages holding a key a row attends, pages the kernel walks), summed
    over the ``nightjar.decode`` spans."""
    valid = walked = 0
    for name, _, _, stats in spans:
        if name == "nightjar.decode":
            valid += stats.get("pages_valid", 0)
            walked += stats.get("pages_walked", 0)
    return valid, walked
