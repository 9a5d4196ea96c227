"""One run of one cell: device check, set-up, window, reference check,
metrics, result line."""
from __future__ import annotations

import gc
import json
import os
import shutil
import sys
import tempfile
import time
from contextlib import nullcontext
from pathlib import Path

import numpy as np

from . import models, readers, trace as tr, traffic
from .peaks import peaks_for

# served tokens compared per run, and the most sequences that may take
SAMPLE_TOKENS = 600
SAMPLE_MAX = 8


class NoChip(RuntimeError):
    pass


def log(tag: str, **fields) -> None:
    print(f"[{tag}] " + json.dumps(fields), file=sys.stderr, flush=True)


def _cell(checkout: Path, name: str):
    bench = json.loads((checkout / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]

    def mine(m):
        return "workloads" not in m or name in m["workloads"]
    return (cell, [m for m in bench["end_to_end"] if mine(m)],
            [m for m in bench["per_layer"] if mine(m)])


def _device(jax, cell: dict, rehearse: bool):
    devs = jax.devices()
    dev = devs[0]
    if rehearse:
        if dev.platform != "cpu":
            raise NoChip("--rehearse runs on the CPU only")
        return dev, None
    if dev.platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {dev.platform} "
                     f"({dev.device_kind})")
    if len(devs) < cell["chips"]:
        raise NoChip(f"the cell asks for {cell['chips']} chips, JAX finds "
                     f"{len(devs)}")
    return dev, peaks_for(dev.device_kind)


def _compile_cache(jax, checkout: Path) -> str:
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(checkout / ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # every program of a cell stays cached: an evicted one would compile
    # again in a later run's set-up
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


def _sample(win, seed: int):
    """Requests finished in the window: the longest, then others drawn from
    the seed, until SAMPLE_TOKENS served tokens or SAMPLE_MAX sequences."""
    done = [r for r in win.requests.values()
            if r.finished is not None and win.open <= r.finished <= win.close
            and r.served]
    if not done:
        return []
    done.sort(key=lambda r: r.rid)
    longest = max(done, key=lambda r: r.prompt_len + len(r.served))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed) % (1 << 64), 7])
    order = [longest] + [rest[i] for i in rng.permutation(len(rest))]
    out, n = [], 0
    for r in order:
        if n >= SAMPLE_TOKENS or len(out) >= SAMPLE_MAX:
            break
        out.append(r)
        n += len(r.served)
    return out


def check_served(spec: dict, params, sample) -> dict:
    """Widest gap, over the sampled served tokens, between the reference's
    best logit and its logit of the served token."""
    from .reference import served_gaps
    widest, n = 0.0, 0
    for r in sample:
        gaps, _ = served_gaps(spec, params, r.prompt, r.served)
        widest = max(widest, float(gaps.max()))
        n += len(gaps)
    return {"widest_logit_gap": widest, "tokens": n, "sequences": len(sample)}


def verdict(checked: dict, limit, *, rehearse: bool):
    """(correct, checks) of a reading of ``check_served``: the widest gap
    within the configuration's limit, over enough compared tokens.  The
    control (``control.py``) goes through the same decision."""
    # a rehearsal's outputs are a few tokens long
    least = 16 if rehearse else 300
    correct = bool(checked["tokens"] >= least and limit is not None
                   and checked["widest_logit_gap"] <= limit)
    return correct, {
        "widest_logit_gap": {"value": checked["widest_logit_gap"],
                             "limit": limit},
        "tokens_compared": {"value": checked["tokens"], "limit": least}}


def run_cell(checkout: Path, name: str, seed: int, seconds: float, *,
             trace: bool, rehearse: bool, t_start: float) -> dict:
    cell, e2e, per_layer = _cell(checkout, name)
    conf = models.load_config(cell["config"])
    mix = traffic.load_traffic(cell["traffic"])
    import jax
    dev, peaks = _device(jax, cell, rehearse)
    src = checkout / "src"
    if not (src / "repro").is_dir():
        raise SystemExit(f"the program's sources are missing ({src})")
    sys.path.insert(0, str(src))
    cache = _compile_cache(jax, checkout)
    from repro.kernels import ops
    from . import driver as drv, system

    tspec, dspec = conf["target"], conf["draft"]
    if rehearse:
        tspec, dspec = models.rehearsal_spec(tspec), models.rehearsal_spec(dspec)
        mix = traffic.rehearsal_cut(mix)
    ctx_mgr = ops.interpret_kernels() if rehearse else nullcontext()
    with ctx_mgr:
        engine, tparams, _ = system.build(conf, mix, seed, rehearse=rehearse,
                                          target_spec=tspec, draft_spec=dspec)
        stream = traffic.Stream(mix, seed, tspec["vocab"])
        d = drv.Driver(engine, stream, mix, spans=trace)
        trace_dir = tempfile.mkdtemp(prefix="chip-trace-") if trace else None
        with drv.CompileCounter() as counter:
            warm = d.warm_up(counter)
            setup_s = time.perf_counter() - t_start
            log("setup", setup_s=setup_s, compile_cache=cache,
                lowered=counter.count, compile_s=counter.seconds, **warm)
            pre0 = d.preemptions
            if trace:
                jax.profiler.start_trace(trace_dir)
                with jax.profiler.TraceAnnotation("bench.window"):
                    win = d.window(seconds, counter)
                jax.profiler.stop_trace()
            else:
                win = d.window(seconds, counter)
        preempted = d.preemptions - pre0
        stats = dev.memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        mm_events = win.memory_events
        pool_blocks = engine.scheduler.bm.total_blocks
        # the program's state goes before the reference runs
        del engine, d
        gc.collect()
        sample = _sample(win, seed)
        checked = check_served(tspec, tparams, sample)
    del tparams
    tdata = None
    if trace:
        ops_by_dev, spans = tr.read_xplane(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        wspan = [s for s in spans if s[0] == "bench.window"]
        w0, w1 = (wspan[0][1], wspan[0][1] + wspan[0][2]) if wspan else (
            min(s[1] for s in spans), max(s[1] + s[2] for s in spans))
        dev_ops = tr.clip(ops_by_dev.get(f"/device:TPU:{dev.id}", []), w0, w1)
        tdata = {"ops": dev_ops, "spans": tr.clip(spans, w0, w1),
                 "t0": w0, "t1": w1}
    ctx = readers.Context(win, tspec, dspec, peaks, setup_s, preempted, tdata)

    _report(win, ctx, mm_events, pool_blocks)
    correct, checks = verdict(checked, conf["logit_gap_limit"],
                              rehearse=rehearse)
    attempted = len(ctx.due_in_window())
    wanted = per_layer if trace else e2e
    metrics = {}
    for m in wanted:
        v = readers.load_reader(m["name"])(ctx)
        if v is None:
            continue
        metrics[m["name"]] = {"value": None if rehearse else float(v),
                              "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": attempted, "failed": 0,
              "metrics": metrics, "device": device}
    if trace:
        busy = tr.busy_seconds(tdata["ops"])
        device.update(busy_s=busy, window_s=tdata["t1"] - tdata["t0"])
        result["breakdown"] = {
            "device_ops": tr.top_ops(tdata["ops"]),
            "idle_gaps": tr.idle_gaps(tdata["ops"], tdata["spans"],
                                      tdata["t0"], tdata["t1"])}
    for k, v in checks.items():
        print(f"check {k} = {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)
    result["checks"] = checks
    return result


def gamma0_step_seconds(steps) -> dict:
    """Mean wall seconds of a decode step without speculation, by padded
    table width: what one more doubling of the width costs."""
    by: dict = {}
    for s in steps:
        if s.gamma == 0 and not s.admitted:
            by.setdefault(s.width, []).append(s.t1 - s.t0)
    return {w: {"n": len(v), "mean_s": sum(v) / len(v)}
            for w, v in sorted(by.items())}


def _report(win, ctx, mm_events, pool_blocks) -> None:
    """What the run has worth printing besides the metrics: lines on
    standard error, before the checks."""
    from .stats import percentile
    hist: dict = {}
    widths: dict = {}
    for s in ctx.decode_steps():
        hist[s.gamma] = hist.get(s.gamma, 0) + 1
        widths[s.width] = widths.get(s.width, 0) + 1
    log("window", seconds=ctx.seconds, steps=len(win.steps),
        lowered_in_window=win.lowerings, gamma_histogram=hist,
        table_width_histogram=widths,
        steps_with_prefill=sum(1 for s in win.steps if s.admitted),
        pool_blocks_at_close=pool_blocks,
        gamma0_step_s_by_width=gamma0_step_seconds(ctx.decode_steps()))
    due = ctx.due_in_window()
    tt = [r.first_token - r.due for r in due if r.first_token is not None]
    fin = [r for r in win.requests.values()
           if r.finished is not None and ctx.in_window(r.finished)]
    log("requests", due_in_window=len(due), with_first_token=len(tt),
        finished_in_window=len(fin),
        ttft_p50_s=percentile(tt, 0.5) if tt else None,
        ttft_p99_s=percentile(tt, 0.99) if tt else None,
        e2e_p90_s=percentile([r.finished - r.due for r in fin], 0.9)
        if fin else None)
    log("memory_manager", events=[list(e[:3]) for e in mm_events])
