"""Profiler trace of the window, and its reduction to numbers.

``read_xplane`` turns the profiler's ``.xplane.pb`` into plain event lists:
device operations (from the TPU planes' "XLA Ops" lines) and host spans
(the ``TraceAnnotation`` spans the harness put around its calls into the
engine, the backend and the memory manager).  Everything after that is
plain arithmetic on (name, start, duration) tuples in seconds, which the
tests check on a small recorded trace."""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Tuple

Event = Tuple[str, float, float]        # (name, start s, duration s)

HOST_SPANS = ("bench.window", "engine.step", "backend.prefill", "backend.step",
              "backend.draft_catchup", "memmgr.offload", "memmgr.reload",
              "memmgr.migrate", "memmgr.grow", "memmgr.shrink")


def read_xplane(trace_dir: str) -> Tuple[Dict[str, List[Event]], List[Event]]:
    """({device plane: ops}, host spans) of the newest trace under
    ``trace_dir``; times in seconds on the profiler's clock."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    device: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    ops.append((ev.name, ev.start_ns * 1e-9,
                                ev.duration_ns * 1e-9))
            device[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HOST_SPANS:
                        host.append((ev.name, ev.start_ns * 1e-9,
                                     ev.duration_ns * 1e-9))
    return device, host


def clip(events: Iterable[Event], t0: float, t1: float) -> List[Event]:
    """Events cut to [t0, t1]; those wholly outside are dropped."""
    out = []
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((name, a, b - a))
    return out


def union(events: Iterable[Event]) -> List[Tuple[float, float]]:
    """Disjoint (start, end) intervals covered by at least one event."""
    iv = sorted((s, s + d) for _, s, d in events if d > 0)
    merged: List[List[float]] = []
    for a, b in iv:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_seconds(events: Iterable[Event]) -> float:
    return sum(b - a for a, b in union(events))


def op_name(event_name: str) -> str:
    """The HLO instruction's name out of a device op event's full text
    (``%fusion.12 = bf16[...] fusion(...), calls=...`` -> ``fusion.12``)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def top_ops(events: Iterable[Event], n: int = 10) -> List[list]:
    """The n device operations that took most time, summed by instruction
    name."""
    tot: Dict[str, float] = defaultdict(float)
    for name, _, d in events:
        tot[op_name(name)] += d
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def kernel_seconds(events: Iterable[Event], kernel: str) -> float:
    """Summed device time of the operations whose instruction is the
    kernel (``kernel``, ``kernel.<n>``); an operation that only reads the
    kernel's output (``%copy.7 = ... copy(%paged_attention.1)``) is not the
    kernel."""
    return sum(d for name, _, d in events
               if op_name(name).split(".", 1)[0] == kernel)


def idle_gaps(events: Iterable[Event], host: Iterable[Event], t0: float,
              t1: float, n: int = 10) -> List[list]:
    """The n longest stretches of [t0, t1] with no device operation, each
    named by the innermost host span that covers most of it ("no span"
    where the harness was outside every span, e.g. waiting for a
    request)."""
    busy = union(events)
    gaps, cur = [], t0
    for a, b in busy:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if t1 > cur:
        gaps.append((cur, t1))
    gaps.sort(key=lambda g: g[0] - g[1])
    spans = list(host)
    out = []
    for a, b in gaps[:n]:
        best, key = "no span", (0.0, 0.0)
        for name, s, d in spans:
            cover = min(b, s + d) - max(a, s)
            # most cover first; on equal cover the shorter, inner span
            k = (round(cover, 9), -d)
            if cover > 0 and k > key:
                best, key = name, k
        out.append([best, b - a])
    return out
