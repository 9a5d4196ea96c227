"""Arithmetic shared by the metric readers in ``metrics/``.

A reader is ``metrics/<metric name>.py`` with ``read(ctx) -> float | None``;
``None`` means it found nothing to read, and the harness then leaves the
metric out.  ``ctx`` is a :class:`Context`."""
from __future__ import annotations

import importlib.util
import sys
from dataclasses import dataclass
from typing import List, Optional

from . import counts
from .models import CHIP_DIR


@dataclass
class Context:
    window: object            # driver.Window
    target: dict              # model specs (harness.models)
    draft: dict
    peaks: Optional[dict]     # None off the chip
    setup_s: float
    preemptions: int
    trace: Optional[dict]     # {"ops", "spans", "t0", "t1"}, traced run only

    @property
    def seconds(self) -> float:
        return self.window.close - self.window.open

    def in_window(self, t: float) -> bool:
        return self.window.open <= t <= self.window.close

    def due_in_window(self) -> List:
        w = self.window
        return [r for r in w.requests.values() if w.open <= r.due < w.close]

    def decode_steps(self) -> List:
        return [s for s in self.window.steps if s.kind == "decode"]

    def token_times(self, r) -> List[float]:
        """Wall time of each of the request's tokens inside the window."""
        out = []
        for t, n in r.stamps:
            if self.in_window(t):
                out.extend([t] * n)
        return out


def load_reader(name: str):
    mod_name = "chip_metric_" + name.replace(".", "_").replace("-", "_")
    if mod_name not in sys.modules:
        path = CHIP_DIR / "metrics" / f"{name}.py"
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[mod_name] = mod
    return sys.modules[mod_name].read


def target_flops_in_window(ctx: Context) -> float:
    """Target forward FLOPs of the work the window delivered: each prompt
    whose first prefill fell in the window, and each output token first
    delivered in it (token m of a request, 1-based, sits at position
    P + m - 1: the first comes out of the prompt's last position and adds
    only the output head)."""
    s = ctx.target
    total = 0.0
    for r in ctx.window.requests.values():
        if r.admitted and ctx.in_window(r.admitted[0]):
            total += counts.prompt_flops(s, r.prompt_len)
        m = 0
        for t, n in r.stamps:
            for _ in range(n):
                m += 1
                if not ctx.in_window(t):
                    continue
                total += (counts.head_flops(s) if m == 1 else
                          counts.token_flops(s, r.prompt_len + m - 1))
    return total


def attention_roofline_seconds(ctx: Context) -> float:
    """Least time the paged-attention kernel calls of the window needed:
    one call per layer of each model in each backend call, with the rows
    each call serves (valid tokens only), each kind of layer counted by
    its own keys."""
    t, d, pk = ctx.target, ctx.draft, ctx.peaks
    secs = 0.0

    def add(spec, rows):
        nonlocal secs
        for layers, f, b in counts.attention_calls(spec, rows):
            secs += layers * counts.roofline_seconds(f, b, pk)

    for at, kind, rec in ctx.window.backend_calls:
        if not ctx.in_window(at):
            continue
        if kind == "prefill":
            rows = [(p, p) for p in rec["prompts"]]
            add(t, rows)
            if rec["draft"]:
                add(d, rows)
        elif kind == "step":
            g, ctxs = rec["gamma"], rec["ctx"]
            if g == 0:
                add(t, [(1, c + 1) for c in ctxs])
            else:
                for j in range(g + 1):
                    add(d, [(1, c + j + 1) for c in ctxs])
                add(t, [(g + 1, c + g + 1) for c in ctxs])
        elif kind == "draft_catchup":
            if rec["rows"]:
                add(d, [(dl, c) for dl, c in rec["rows"]])
    return secs
