"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV per the repo contract:
  * us_per_call — wall-clock microseconds of the benchmarked call (for the
    sim-tier serving runs this is the bench wall time; for kernels it is the
    per-op latency),
  * derived — the paper-facing metric (tokens/s, latency, regret slope, ...).

Run everything:   PYTHONPATH=src python -m benchmarks.run
Single item:      PYTHONPATH=src python -m benchmarks.run --only table5
Fast smoke:       PYTHONPATH=src python -m benchmarks.run --fast
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

import numpy as np

from .common import (CSV, PAIRS, POLICIES, POLICY_LABEL, VICUNA_13B,
                     VICUNA_68M, bench_out, run_cluster, run_serving,
                     saturated_gamma_stats, timed)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import configs  # noqa: E402
from repro.core.bandits import make_policy  # noqa: E402
from repro.core.cswitch import CSwitchTable  # noqa: E402
from repro.core.planner import NightjarPlanner  # noqa: E402
from repro.models import registry  # noqa: E402
from repro.serving.costmodel import (RTX_4090, RooflineCostModel)  # noqa: E402
from repro.serving.workload import dynamic_rate_trace  # noqa: E402


# ---------------------------------------------------------------------------
# Figure 2: throughput vs request rate for fixed speculative lengths
# ---------------------------------------------------------------------------


def fig2_fixed_gamma(csv: CSV, fast: bool):
    rates = [5, 15, 25] if fast else [2, 5, 10, 15, 20, 25, 30]
    gammas = [0, 1, 3, 5]
    for rate in rates:
        n = max(int(rate * (8 if fast else 15)), 40)
        for g in gammas:
            t0 = time.perf_counter()
            m, _ = run_serving("7b", f"fixed-{g}" if g else "ar", rate=rate,
                               n=n, dataset="sharegpt")
            csv.add(f"fig2.qps{rate}.gamma{g}",
                    (time.perf_counter() - t0) * 1e6,
                    f"throughput={m.throughput:.1f}tok/s")


# ---------------------------------------------------------------------------
# Figure 9 + Tables 5/6: method comparison
# ---------------------------------------------------------------------------


def table5_table6(csv: CSV, fast: bool):
    pairs = ["7b"] if fast else ["7b", "13b"]
    datasets = ["sharegpt"] if fast else ["alpaca", "sharegpt", "specbench"]
    trace = dynamic_rate_trace(duration_s=40 if fast else 90,
                               low=3, high=28, period_s=20)
    for pair in pairs:
        for ds in datasets:
            n = 150 if fast else 400
            for pol in POLICIES:
                t0 = time.perf_counter()
                m, _ = run_serving(pair, pol, trace=trace, n=n, dataset=ds)
                csv.add(f"table5.{pair}.{ds}.{POLICY_LABEL[pol]}",
                        (time.perf_counter() - t0) * 1e6,
                        f"throughput={m.throughput:.1f}tok/s")
                csv.add(f"table6.{pair}.{ds}.{POLICY_LABEL[pol]}", 0.0,
                        f"mean_latency={m.mean_latency*1e3:.0f}ms;"
                        f"ttft={m.mean_ttft*1e3:.0f}ms")


def fig9_low_high(csv: CSV, fast: bool):
    for label, rate in (("low", 3), ("high", 28)):
        n = max(int(rate * (10 if fast else 20)), 50)
        for pol in POLICIES:
            t0 = time.perf_counter()
            m, _ = run_serving("7b", pol, rate=rate, n=n, dataset="sharegpt")
            csv.add(f"fig9.{label}.{POLICY_LABEL[pol]}",
                    (time.perf_counter() - t0) * 1e6,
                    f"throughput={m.throughput:.1f}tok/s")


# ---------------------------------------------------------------------------
# Figure 11: throughput trace under the dynamic request-rate trace
# ---------------------------------------------------------------------------


def fig11_dynamic_trace(csv: CSV, fast: bool):
    trace = dynamic_rate_trace(duration_s=40 if fast else 80, low=3, high=25,
                               period_s=20)
    n = 200 if fast else 500
    for pol in (["ar", "sd", "nightjar"] if fast else POLICIES):
        m, _ = run_serving("7b", pol, trace=trace, n=n, dataset="sharegpt",
                           record_timeline=True)
        # bucket the timeline into 5s windows
        win, acc = {}, {}
        for r in m.timeline:
            w = int(r["t"] // 5)
            win[w] = win.get(w, 0) + r["tokens"]
        series = [round(win.get(w, 0) / 5.0, 1)
                  for w in range(int(m.elapsed // 5) + 1)]
        csv.add(f"fig11.{POLICY_LABEL[pol]}", 0.0,
                "trace_tok_s=" + "|".join(str(s) for s in series[:24]))


# ---------------------------------------------------------------------------
# Figure 12: bandit-method ablation
# ---------------------------------------------------------------------------


def fig12_bandit_ablation(csv: CSV, fast: bool):
    datasets = ["sharegpt"] if fast else ["alpaca", "sharegpt", "specbench"]
    pols = ["eps-greedy", "linucb", "banditspec", "ada-bingreedy", "nightjar"]
    for ds in datasets:
        for rate in ([5, 25] if fast else [3, 10, 25]):
            n = max(int(rate * 12), 60)
            for pol in pols:
                m, _ = run_serving("7b", pol, rate=rate, n=n, dataset=ds)
                csv.add(f"fig12.{ds}.qps{rate}.{POLICY_LABEL[pol]}", 0.0,
                        f"throughput={m.throughput:.1f}tok/s")


# ---------------------------------------------------------------------------
# Figure 13: offload ablation (throughput + TTFT), Figure 14: threshold sweep
# ---------------------------------------------------------------------------


def fig13_offload(csv: CSV, fast: bool):
    # memory pressure: small KV reserve + high rate on the 24GB card
    for rate in ([30] if fast else [20, 30, 35]):
        n = max(int(rate * (10 if fast else 18)), 80)
        for off in (True, False):
            m, eng = run_serving("7b", "nightjar", rate=rate, n=n,
                                 dataset="sharegpt", enable_offload=off,
                                 kv_reserve_frac=0.35)
            name = "offload" if off else "no-offload"
            csv.add(f"fig13.qps{rate}.{name}", 0.0,
                    f"throughput={m.throughput:.1f}tok/s;"
                    f"ttft={m.mean_ttft*1e3:.0f}ms;"
                    f"offloads={m.offload_events};reloads={m.reload_events}")


def fig14_threshold(csv: CSV, fast: bool):
    fracs = [0.05, 0.1, 0.2] if fast else [0.02, 0.05, 0.1, 0.2, 0.4]
    for frac in fracs:
        m, _ = run_serving("7b", "nightjar", rate=28, n=250,
                           dataset="sharegpt", tau_low_frac=frac,
                           kv_reserve_frac=0.35)
        csv.add(f"fig14.tau{int(frac*100)}pct", 0.0,
                f"throughput={m.throughput:.1f}tok/s")


# ---------------------------------------------------------------------------
# Figure 15: Nightjar vs every fixed gamma (13B)
# ---------------------------------------------------------------------------


def fig15_fixed_vs_adaptive(csv: CSV, fast: bool):
    rates = [5, 20] if fast else [3, 8, 15, 25]
    for rate in rates:
        n = max(int(rate * 12), 60)
        best_fixed, best_name = 0.0, ""
        for g in range(0, 6):
            m, _ = run_serving("13b", f"fixed-{g}" if g else "ar",
                               rate=rate, n=n, dataset="specbench")
            if m.throughput > best_fixed:
                best_fixed, best_name = m.throughput, f"gamma{g}"
            csv.add(f"fig15.qps{rate}.gamma{g}", 0.0,
                    f"throughput={m.throughput:.1f}tok/s")
        m, _ = run_serving("13b", "nightjar", rate=rate, n=n,
                           dataset="specbench")
        csv.add(f"fig15.qps{rate}.nightjar", 0.0,
                f"throughput={m.throughput:.1f}tok/s;"
                f"best_fixed={best_name}:{best_fixed:.1f}")


# ---------------------------------------------------------------------------
# Chunked-prefill hybrid batching: monolithic vs chunked tail latency
# ---------------------------------------------------------------------------


def prefill_hybrid(csv: CSV, fast: bool):
    """Monolithic vs chunked prefill at {low,high} arrival rate.

    The high-rate cell is the paper's dynamic-load, compute-bound regime:
    monolithic admission prefills whole prompt batches in one call and every
    running sequence stalls behind them (head-of-line blocking), which shows
    up as p99 TTFT / SLO-goodput — exactly the tail the chunked token-budget
    scheduler is built to fix.  Reports p50/p99 TTFT, SLO attainment and
    goodput for each cell.  The budget is TOTAL step tokens (Sarathi
    decode-token accounting): 384 = ~128 decode slots at saturation plus a
    256-token prefill share."""
    chunk = 384
    cells = (("low", 8), ("high", 80))
    for label, rate in cells:
        n = max(int(rate * (2 if fast else 5)), 30)
        for mode, ct in (("monolithic", 0), (f"chunk{chunk}", chunk)):
            t0 = time.perf_counter()
            m, _ = run_serving("7b", "nightjar", rate=rate, n=n,
                               dataset="alpaca", chunk_tokens=ct)
            csv.add(f"prefill.{label}.{mode}",
                    (time.perf_counter() - t0) * 1e6,
                    f"p50_ttft={m.ttft_percentile(0.5)*1e3:.0f}ms;"
                    f"p99_ttft={m.ttft_percentile(0.99)*1e3:.0f}ms;"
                    f"slo_att={m.slo_attainment:.3f};"
                    f"goodput={m.goodput:.1f}tok/s;"
                    f"throughput={m.throughput:.1f}tok/s")


# ---------------------------------------------------------------------------
# Prefix-sharing copy-on-write KV caching: templated vs disjoint workloads
# ---------------------------------------------------------------------------


def prefix_grid(csv: CSV, fast: bool):
    """Prefix caching on the templated workload: {templated, disjoint} x
    {caching on, off} x {low, high} arrival rate, chunked scheduler.

    The headline cell is templated.high: every prompt repeats a 512-token
    system prompt, so caching-off re-stores identical prefix blocks per
    request AND re-runs identical prefill compute — copy-on-write sharing
    reclaims both, which shows up as strictly lower p99 TTFT and strictly
    fewer allocated blocks with byte-identical per-request committed token
    streams.  The disjoint rows (template_len=0, same length shapes) are the
    control: caching buys ~nothing when prompts never repeat.  Persists the
    grid to BENCH_prefix.json."""
    import hashlib

    from repro.serving.workload import templated_requests

    chunk = 384
    results = {"chunk_tokens": chunk, "template_len": 512, "grid": {}}
    cells = (("low", 8), ("high", 80))
    for wl, template in (("templated", 512), ("disjoint", 0)):
        for label, rate in cells:
            n = max(int(rate * (2 if fast else 5)), 30)
            reqs = templated_requests(rate, n, template_len=template, seed=1)
            for caching in (False, True):
                mode = "cache" if caching else "nocache"
                t0 = time.perf_counter()
                m, _ = run_serving("7b", "nightjar", chunk_tokens=chunk,
                                   prefix_caching=caching, requests=reqs)
                wall = (time.perf_counter() - t0) * 1e6
                stream = sorted((r.req_id, r.tokens) for r in m.requests)
                sha = hashlib.sha256(repr(stream).encode()).hexdigest()[:16]
                hit = m.prefix_hit_rate
                row = {
                    "p50_ttft_s": m.ttft_percentile(0.5),
                    "p99_ttft_s": m.ttft_percentile(0.99),
                    "slo_attainment": m.slo_attainment,
                    "goodput_tok_s": m.goodput,
                    "throughput_tok_s": m.throughput,
                    "blocks_allocated": m.blocks_allocated,
                    "total_tokens": m.total_tokens,
                    "finished": len(m.requests),
                    "prefix_hit_rate": hit,
                    "saved_prefill_tokens": m.prefix.get("saved_tokens", 0),
                    "forks": m.prefix.get("forks", 0),
                    "tokens_sha": sha,
                }
                results["grid"][f"{wl}.{label}.{mode}"] = row
                csv.add(f"prefix.{wl}.{label}.{mode}", wall,
                        f"p99_ttft={row['p99_ttft_s']*1e3:.0f}ms;"
                        f"blocks={row['blocks_allocated']};"
                        f"goodput={row['goodput_tok_s']:.1f}tok/s;"
                        f"hit_rate={hit:.3f};tokens_sha={sha}")
    with open(bench_out("BENCH_prefix.json"), "w") as f:
        json.dump(results, f, indent=1)


# ---------------------------------------------------------------------------
# Host-memory KV offload tier: multi-turn session workload
# ---------------------------------------------------------------------------


def sessions_grid(csv: CSV, fast: bool):
    """Host KV offload on the multi-turn session workload: {offload, none}
    at a FIXED device pool, chunked scheduler, prefix caching on.

    Each session opens with a long context and returns after think-time
    gaps with its whole history as the prompt.  Between turns the device
    LRU evicts the session's prefix blocks under pressure from other
    sessions; without the host tier the next turn re-runs prefill over the
    full history, with it the blocks restore from host memory into free
    device blocks at PCIe cost.  The headline: warm-turn (turn > 0) p50/p99
    TTFT strictly below cold-turn TTFT and cross-turn hit rate > 0.8 with
    offload on, with byte-identical committed token streams vs offload-off
    (restores change WHERE bytes live, never WHAT is computed).  Persists
    the grid to BENCH_sessions.json."""
    import hashlib

    from repro.serving.request import percentile
    from repro.serving.workload import session_requests

    chunk = 384
    n_sessions, turns, num_blocks = (8, 5, 512) if fast else (16, 6, 768)
    rate = 0.5
    results = {"chunk_tokens": chunk, "sessions": n_sessions, "turns": turns,
               "rate_qps": rate, "num_blocks": num_blocks, "grid": {}}
    reqs = session_requests(n_sessions, turns=turns, rate_qps=rate, seed=0)
    for kv_off in (False, True):
        mode = "offload" if kv_off else "none"
        t0 = time.perf_counter()
        m, eng = run_serving("7b", "nightjar", chunk_tokens=chunk,
                             prefix_caching=True, requests=reqs,
                             enable_offload=False, num_blocks=num_blocks,
                             kv_offload=kv_off)
        wall = (time.perf_counter() - t0) * 1e6
        eng.scheduler.bm.check_invariants()
        stream = sorted((r.req_id, r.tokens) for r in m.requests)
        sha = hashlib.sha256(repr(stream).encode()).hexdigest()[:16]
        warm = [r for r in m.requests if r.turn > 0]
        cold = [r for r in m.requests if r.turn == 0]
        wttft = [r.ttft for r in warm]
        cttft = [r.ttft for r in cold]
        hit = (sum(1 for r in warm if r.cached_tokens > 0)
               / max(len(warm), 1))
        row = {
            "p50_warm_ttft_s": percentile(wttft, 0.5),
            "p99_warm_ttft_s": percentile(wttft, 0.99),
            "p50_cold_ttft_s": percentile(cttft, 0.5),
            "p99_cold_ttft_s": percentile(cttft, 0.99),
            "warm_turns": len(warm),
            "cold_turns": len(cold),
            "cross_turn_hit_rate": hit,
            "prefix_hit_rate": m.prefix_hit_rate,
            "host_spills": m.host.get("spills", 0),
            "host_restores": m.host.get("restores", 0),
            "host_restore_s": m.host.get("restore_s", 0.0),
            "restored_blocks": m.prefix.get("restored_blocks", 0),
            "throughput_tok_s": m.throughput,
            "goodput_tok_s": m.goodput,
            "slo_attainment": m.slo_attainment,
            "finished": len(m.requests),
            "tokens_sha": sha,
        }
        results["grid"][mode] = row
        csv.add(f"sessions.{mode}", wall,
                f"warm_p50={row['p50_warm_ttft_s']*1e3:.0f}ms;"
                f"warm_p99={row['p99_warm_ttft_s']*1e3:.0f}ms;"
                f"cold_p50={row['p50_cold_ttft_s']*1e3:.0f}ms;"
                f"cold_p99={row['p99_cold_ttft_s']*1e3:.0f}ms;"
                f"xturn_hit={hit:.3f};"
                f"restores={row['host_restores']};tokens_sha={sha}")
    with open(bench_out("BENCH_sessions.json"), "w") as f:
        json.dump(results, f, indent=1)


# ---------------------------------------------------------------------------
# Cluster tier: replica-count x arrival-rate grid (the fleet scenario)
# ---------------------------------------------------------------------------


def _gamma_trace(metrics, *, window_s: float = 2.0, max_windows: int = 16):
    """Mean gamma per virtual-time window — the per-replica gamma trace."""
    acc, cnt = {}, {}
    for r in metrics.timeline:
        w = int(r["t"] // window_s)
        acc[w] = acc.get(w, 0) + r["gamma"]
        cnt[w] = cnt.get(w, 0) + 1
    ws = sorted(acc)[:max_windows]
    return "|".join(f"{acc[w] / cnt[w]:.1f}" for w in ws)


def cluster_sweep(csv: CSV, fast: bool):
    """Weak-scaling grid: {1,2,4} replicas x {low,high} per-replica rate.

    The total arrival rate scales with replica count (every replica sees the
    same offered load), so the high cell keeps every replica saturated: each
    replica's planner must independently learn gamma -> 0 while the low cell
    keeps speculation on.  Emits per-replica gamma traces, saturated-regime
    gamma stats and the planner's final exploit arm for the full batch."""
    max_batch = 256
    reps_list = (1, 2) if fast else (1, 2, 4)
    dur = 6 if fast else 12
    agg = {}
    for n_rep in reps_list:
        for label, rate_per in (("low", 4), ("high", 200)):
            rate = rate_per * n_rep
            n = max(int(rate * dur), 40)
            t0 = time.perf_counter()
            m, cl = run_cluster("7b", n_rep, "nightjar", router="jsq",
                                rate=rate, n=n, dataset="alpaca",
                                max_batch=max_batch, record_timeline=True)
            agg[(n_rep, label)] = m.throughput
            sat, arms = [], []
            for i, rm in enumerate(m.per_replica):
                g, f0 = saturated_gamma_stats(rm, max_batch)
                sat.append(f"r{i}:{'-' if g is None else f'{g:.2f}/{f0:.2f}'}")
                pol = cl.replicas[i].policy
                arms.append(str(pol._eq4(pol.bucket(max_batch), 0, max_batch))
                            if hasattr(pol, "_eq4") else "-")
            csv.add(f"cluster.reps{n_rep}.{label}",
                    (time.perf_counter() - t0) * 1e6,
                    f"throughput={m.throughput:.1f}tok/s;"
                    f"sat_gamma={','.join(sat)};"
                    f"exploit_arm={','.join(arms)};"
                    f"requests={'/'.join(map(str, m.replica_counts()))}")
            for i, rm in enumerate(m.per_replica):
                csv.add(f"cluster.reps{n_rep}.{label}.gamma_trace.r{i}", 0.0,
                        f"trace={_gamma_trace(rm)}")
    hi = reps_list[-1]
    csv.add("cluster.weak_scaling", 0.0,
            f"reps{hi}_vs_reps1_high="
            f"{agg[(hi, 'high')] / agg[(1, 'high')]:.2f}x;"
            f"reps{hi}_vs_reps1_low="
            f"{agg[(hi, 'low')] / agg[(1, 'low')]:.2f}x")


def control_grid(csv: CSV, fast: bool):
    """Cluster control plane: {static, autoscale} fleets x
    {rr, kv, slo, affinity} routers x {templated, bursty} traces.

    Templated arm (static 2-replica fleet, prefix caching on, chunked):
    a multi-template workload where sticky affinity routing partitions the
    template population across replicas — each replica's prefix cache
    specialises, which shows up as strictly higher aggregate hit rate and
    strictly lower p99 TTFT than KV-headroom routing, with identical
    per-request committed token counts (the acceptance criterion; the sim
    tier commits counts, not token contents).

    Bursty arm (baseline -> spike -> drain): the elastic fleet (autoscale
    1 -> 2 replicas + admission control) against the static 2-replica
    fleet at EQUAL peak replica count.  During the spike the offered load
    exceeds even the full fleet; the static fleet admits everything and
    lets the queue collapse its tail, while the control plane sheds the
    hopeless arrivals at the door and keeps admitted traffic inside the
    deadline — strictly higher SLO attainment of admitted traffic (shed
    requests reported separately), at fewer replica-seconds.

    Persists the grid to BENCH_control.json."""
    import hashlib

    from repro.serving.workload import bursty_trace, templated_requests

    # per-arm scheduler configs differ (the templated arm exercises the
    # prefix cache through the chunked path; the bursty arm is the plain
    # monolithic fleet) — record each arm's config so rows are only ever
    # compared within their arm
    results = {
        "templated": {"chunk_tokens": 384, "template_len": 512,
                      "num_templates": 8, "prefix_caching": True,
                      "replicas": 2},
        "bursty": {"chunk_tokens": 0, "prefix_caching": False,
                   "dataset": "alpaca", "peak_replicas": 2,
                   "trace": "baseline 4qps -> spike 240qps -> drain 2qps"},
        "grid": {},
    }
    routers = ("rr", "kv", "slo", "affinity")

    # -- templated arm: static 2-replica fleet, caching on ---------------
    n_t = 140 if fast else 360
    treqs = templated_requests(60, n_t, num_templates=8, seed=1)
    for router in routers:
        t0 = time.perf_counter()
        m, cl = run_cluster("7b", 2, "nightjar", router=router,
                            requests=treqs, chunk_tokens=384,
                            prefix_caching=True)
        wall = (time.perf_counter() - t0) * 1e6
        stream = sorted((r.req_id, r.tokens) for r in m.requests)
        sha = hashlib.sha256(repr(stream).encode()).hexdigest()[:16]
        row = {
            "p50_ttft_s": m.ttft_percentile(0.5),
            "p99_ttft_s": m.ttft_percentile(0.99),
            "slo_attainment": m.slo_attainment,
            "goodput_tok_s": m.goodput,
            "prefix_hit_rate": m.prefix_hit_rate,
            "blocks_allocated": sum(r.blocks_allocated
                                    for r in m.per_replica),
            "finished": len(m.requests),
            "replica_requests": m.replica_counts(),
            "spills": getattr(cl.router, "spills", 0),
            "tokens_sha": sha,
        }
        results["grid"][f"templated.static.{router}"] = row
        csv.add(f"control.templated.static.{router}", wall,
                f"p99_ttft={row['p99_ttft_s']*1e3:.0f}ms;"
                f"hit_rate={row['prefix_hit_rate']:.3f};"
                f"slo_att={row['slo_attainment']:.3f};"
                f"tokens_sha={sha}")

    # -- bursty arm: static vs elastic at equal peak replica count -------
    trace = bursty_trace(base=4, spike=240, base_s=12 if fast else 20,
                         spike_s=6 if fast else 12,
                         drain_s=20 if fast else 30, drain=2, seed=2)
    n_b = 1560 if fast else 3040
    breqs = trace.sample_requests(n_b, dataset="alpaca", seed=3)
    bursty_routers = ("kv", "slo") if fast else routers
    for fleet in ("static", "autoscale"):
        kw = dict(requests=breqs)
        if fleet == "autoscale":
            kw.update(shed_factor=1.5,
                      autoscale=dict(min_replicas=1, max_replicas=2,
                                     window_s=8.0))
        for router in bursty_routers:
            t0 = time.perf_counter()
            m, cl = run_cluster("7b", 2, "nightjar", router=router, **kw)
            wall = (time.perf_counter() - t0) * 1e6
            s = m.summary()
            row = {
                "p50_ttft_s": m.ttft_percentile(0.5),
                "p99_ttft_s": m.ttft_percentile(0.99),
                "slo_attainment": m.slo_attainment,
                "slo_attainment_offered": m.slo_attainment_offered,
                "goodput_tok_s": m.goodput,
                "shed": m.shed_count,
                "finished": len(m.requests),
                "peak_replicas": m.peak_replicas,
                "replica_seconds": m.replica_seconds,
                "autoscale_adds": s.get("autoscale", {}).get("adds", 0),
                "autoscale_drains": s.get("autoscale", {}).get("drains", 0),
            }
            results["grid"][f"bursty.{fleet}.{router}"] = row
            csv.add(f"control.bursty.{fleet}.{router}", wall,
                    f"slo_att={row['slo_attainment']:.3f};"
                    f"offered={row['slo_attainment_offered']:.3f};"
                    f"shed={row['shed']};"
                    f"peak_replicas={row['peak_replicas']};"
                    f"replica_s={row['replica_seconds']:.0f}")

    with open(bench_out("BENCH_control.json"), "w") as f:
        json.dump(results, f, indent=1)


def disagg_grid(csv: CSV, fast: bool):
    """Disaggregated prefill/decode fleet vs the colocated fleet at EQUAL
    fleet size (4 replicas vs 2 prefill + 2 decode) on the mixed
    long-prompt/long-decode workload.

    The high cells are the slot-clogging regime: with a bounded admission
    batch, colocated replicas' slots fill with long-lived decodes, so long
    prompts queue behind residents and p99 TTFT collapses — while the
    disaggregated prefill pool hands every finished prompt's KV blocks to a
    decode replica (batched block migration priced at interconnect
    bandwidth) and keeps admitting.  Headline: disagg.high strictly beats
    colocated.high on p99 TTFT AND goodput with byte-identical per-request
    committed token streams (migration changes WHERE decode runs, never
    WHAT is computed).

    The pricedout cells are the fallback demonstration: at low load with a
    pricer margin, the queue-delay forecast saved never covers the modelled
    transfer time, so the control plane declines (nearly) every handoff and
    the 'disaggregated' fleet degrades gracefully to colocated serving —
    never worse by construction.  Persists the grid to BENCH_disagg.json."""
    import hashlib

    from repro.serving.workload import mixed_requests

    chunk, mb, qa = 128, 48, 0.25
    rate_hi, n_hi = 28.0, 500
    rate_lo, n_lo = 6.0, 100 if fast else 150
    results = {"chunk_tokens": chunk, "max_batch": mb, "dataset": "mixed",
               "qa_frac": qa, "replicas": 4, "split": "2 prefill + 2 decode",
               "high": {"rate_qps": rate_hi, "requests": n_hi},
               "pricedout": {"rate_qps": rate_lo, "requests": n_lo,
                             "margin_s": 0.25},
               "grid": {}}
    hi_reqs = mixed_requests(rate_hi, n_hi, qa_frac=qa, seed=1)
    lo_reqs = mixed_requests(rate_lo, n_lo, qa_frac=qa, seed=1)
    cells = (
        ("colocated.high", hi_reqs, None),
        ("disagg.high", hi_reqs, dict(prefill=2, decode=2)),
        ("colocated.low", lo_reqs, None),
        ("disagg.pricedout", lo_reqs,
         dict(prefill=2, decode=2, margin_s=0.25)),
    )
    for name, reqs, disagg in cells:
        t0 = time.perf_counter()
        m, cl = run_cluster("7b", 4, "nightjar", router="jsq",
                            requests=reqs, chunk_tokens=chunk,
                            max_batch=mb, disaggregate=disagg)
        wall = (time.perf_counter() - t0) * 1e6
        stream = sorted((r.req_id, r.tokens) for r in m.requests)
        sha = hashlib.sha256(repr(stream).encode()).hexdigest()[:16]
        row = {
            "p50_ttft_s": m.ttft_percentile(0.5),
            "p99_ttft_s": m.ttft_percentile(0.99),
            "slo_attainment": m.slo_attainment,
            "goodput_tok_s": m.goodput,
            "throughput_tok_s": m.throughput,
            "handoffs": len(m.handoffs),
            "handoffs_declined": m.handoffs_declined,
            "handoff_transfer_s": m.handoff_transfer_s,
            "handoff_fallbacks": m.handoff_fallbacks,
            "replica_seconds": m.replica_seconds,
            "peak_replicas": m.peak_replicas,
            "finished": len(m.requests),
            "tokens_sha": sha,
        }
        results["grid"][name] = row
        csv.add(f"disagg.{name}", wall,
                f"p99_ttft={row['p99_ttft_s']*1e3:.0f}ms;"
                f"slo_att={row['slo_attainment']:.3f};"
                f"goodput={row['goodput_tok_s']:.1f}tok/s;"
                f"handoffs={row['handoffs']};"
                f"declined={row['handoffs_declined']};"
                f"tokens_sha={sha}")
    g = results["grid"]
    results["acceptance"] = {
        "disagg_wins_p99_ttft": (g["disagg.high"]["p99_ttft_s"]
                                 < g["colocated.high"]["p99_ttft_s"]),
        "disagg_wins_goodput": (g["disagg.high"]["goodput_tok_s"]
                                > g["colocated.high"]["goodput_tok_s"]),
        "streams_identical_high": (g["disagg.high"]["tokens_sha"]
                                   == g["colocated.high"]["tokens_sha"]),
        "streams_identical_low": (g["disagg.pricedout"]["tokens_sha"]
                                  == g["colocated.low"]["tokens_sha"]),
        "pricedout_declines": (g["disagg.pricedout"]["handoffs_declined"]
                               > g["disagg.pricedout"]["handoffs"]),
    }
    csv.add("disagg.acceptance", 0.0,
            ";".join(f"{k}={v}" for k, v in results["acceptance"].items()))
    with open(bench_out("BENCH_disagg.json"), "w") as f:
        json.dump(results, f, indent=1)


def chaos_grid(csv: CSV, fast: bool):
    """Chaos gate: crash-and-recover vs the fault-free baseline on the SAME
    seeded workload (2 replicas, alpaca, TTFT SLO).

    The crash cell kills replica 1 mid-run; the failure detector notices
    the silence on the shared virtual clock, a replacement replica spawns
    from the seeded factory, and every in-flight request re-queues through
    the router with exponential backoff and re-prefills from its prompt.
    The chaos cell adds a transient straggler window on replica 0 on top.

    Machine-checked acceptance flags (CI asserts all of them): ZERO
    requests dropped in every cell, committed token streams byte-identical
    to the fault-free run, every crash-lost request re-queued and completed
    (retry budget never exhausted), recovered SLO attainment within a
    bounded gap of baseline, and MTTD/MTTR actually measured (not zero and
    not fabricated when nothing fired).  Persists BENCH_chaos.json."""
    import hashlib

    from repro.serving.workload import poisson_requests

    rate, n = 20.0, (160 if fast else 320)
    results = {"replicas": 2, "dataset": "alpaca", "rate_qps": rate,
               "requests": n, "grid": {}}
    reqs = poisson_requests(rate, n, dataset="alpaca", seed=1)
    cells = [
        ("faultfree", None),
        ("crash", "crash:1@2.0"),
    ]
    if not fast:
        cells.append(("chaos", "crash:1@2.0;straggle:0@1.0..5.0x3"))
    for name, plan in cells:
        t0 = time.perf_counter()
        m, cl = run_cluster("7b", 2, "nightjar", router="jsq",
                            requests=reqs, fault_plan=plan)
        wall = (time.perf_counter() - t0) * 1e6
        stream = sorted((r.req_id, r.tokens) for r in m.requests)
        sha = hashlib.sha256(repr(stream).encode()).hexdigest()[:16]
        row = {
            "p50_ttft_s": m.ttft_percentile(0.5),
            "p99_ttft_s": m.ttft_percentile(0.99),
            "slo_attainment": m.slo_attainment,
            "goodput_tok_s": m.goodput,
            "throughput_tok_s": m.throughput,
            "finished": len(m.requests),
            "crashes": len(m.crashes),
            "requests_lost": sum(c["lost"] for c in m.crashes),
            "requeues": m.requeues,
            "retries": m.retries,
            "failed_requests": len(m.failed_requests),
            "mttd_s": m.mttd,
            "mttr_s": m.mttr,
            "recovery_seconds": m.recovery_seconds,
            "tokens_sha": sha,
        }
        results["grid"][name] = row
        csv.add(f"chaos.{name}", wall,
                f"finished={row['finished']}/{n};"
                f"crashes={row['crashes']};"
                f"requeues={row['requeues']};"
                f"failed={row['failed_requests']};"
                f"slo_att={row['slo_attainment']:.3f};"
                f"mttr={'n/a' if m.mttr is None else f'{m.mttr:.3f}s'};"
                f"tokens_sha={sha}")
    g = results["grid"]
    base = g["faultfree"]
    fault_cells = [g[k] for k in g if k != "faultfree"]
    results["acceptance"] = {
        "zero_dropped": all(c["finished"] == n for c in g.values()),
        "streams_identical": all(c["tokens_sha"] == base["tokens_sha"]
                                 for c in fault_cells),
        "all_requeued_completed": all(
            c["requeues"] > 0 and c["requeues"] == c["requests_lost"]
            and c["failed_requests"] == 0 for c in fault_cells),
        "recovered_slo_bounded": all(
            c["slo_attainment"] >= base["slo_attainment"] - 0.15
            for c in fault_cells),
        "mttr_measured": (all(c["mttr_s"] is not None and c["mttr_s"] > 0
                              for c in fault_cells)
                          and base["mttr_s"] is None),
    }
    csv.add("chaos.acceptance", 0.0,
            ";".join(f"{k}={v}" for k, v in results["acceptance"].items()))
    with open(bench_out("BENCH_chaos.json"), "w") as f:
        json.dump(results, f, indent=1)


def surge_grid(csv: CSV, fast: bool):
    """Surge gate: 3x sustained overload with mixed priority classes and a
    seeded client-cancellation storm, brownout ladder ON vs OFF (2
    replicas, alpaca lengths, per-class SLOs/deadlines).

    Three cells on the SAME seeded workload: ``base`` (no cancellations —
    the stream-identity reference), ``no_brownout`` (storm + classic
    class-blind admission) and ``brownout`` (same storm + class-weighted
    admission + the fleet brownout ladder: gamma->0, draft offload, a
    best_effort output cap, class-ordered shedding).  The plateau is
    deliberately past fleet capacity, so the only question is HOW service
    degrades.

    Machine-checked acceptance flags (CI asserts all of them): brownout
    strictly beats no-brownout on interactive-class offered-SLO attainment
    AND fleet goodput; every request in every cell is accounted per class
    (finished+shed+cancelled+expired+failed == offered); invariants I1-I8
    clean on every replica post-run; surviving committed streams
    byte-identical to the cancellation-free run; and both the
    speculation-off and draft-offload rungs observably fired.  Persists
    BENCH_surge.json."""
    from repro.serving.cluster import FAILED
    from repro.serving.workload import (cancellation_storm, surge_requests,
                                        surge_trace)

    base_s, surge_s, recover_s = (6.0, 14.0, 8.0) if fast else \
        (8.0, 24.0, 12.0)
    base_rate, mult = 60.0, 3.0
    n = int(base_rate * (base_s + recover_s) + base_rate * mult * surge_s)
    trace = surge_trace(base=base_rate, surge_mult=mult, base_s=base_s,
                        surge_s=surge_s, recover_s=recover_s, seed=2)
    reqs = surge_requests(n, trace=trace, dataset="alpaca", seed=1)
    storm = dict(frac=0.12, start=base_s + 2.0, end=base_s + surge_s)
    cancels = cancellation_storm(reqs, seed=4, **storm)
    weights = {"interactive": 1.5, "batch": 0.8, "best_effort": 0.4}
    bo = dict(slo=0.5, enter_factor=1.5, exit_factor=0.8,
              kv_low_frac=0.10, kv_calm_frac=0.30, best_effort_cap=32,
              cooldown_s=1.0, check_interval_s=0.25)
    results = {"replicas": 2, "dataset": "alpaca", "requests": n,
               "trace": {"base_qps": base_rate, "surge_mult": mult,
                         "base_s": base_s, "surge_s": surge_s,
                         "recover_s": recover_s},
               "storm": storm, "cancel_schedule": len(cancels),
               "class_weights": weights, "brownout_cfg": bo, "grid": {}}
    cells = (
        ("base", dict(shed_factor=1.5)),
        ("no_brownout", dict(shed_factor=1.5, cancels=cancels)),
        ("brownout", dict(shed_factor=1.5, class_weights=weights,
                          cancels=cancels, brownout=bo)),
    )

    def offered_att(per_class, cls):
        """SLO attainment over the class's offered load: shed/expired/
        failed count as misses, client cancels are excluded (neither met
        nor missed).  None without samples."""
        b = per_class.get(cls)
        if b is None:
            return None
        denom = b["slo_samples"] + b["shed"] + b["expired"] + b["failed"]
        return b["slo_met"] / denom if denom else None

    toks = {}
    for name, kw in cells:
        t0 = time.perf_counter()
        m, cl = run_cluster("7b", 2, "nightjar", router="jsq",
                            max_batch=256, requests=reqs, **kw)
        wall = (time.perf_counter() - t0) * 1e6
        toks[name] = {r.req_id: r.tokens for r in m.requests}
        per_class = m.class_summary()
        inv_ok = True
        try:
            for i, e in enumerate(cl.replicas):
                e.scheduler.bm.check_invariants(
                    failed=cl.state[i] == FAILED)
        except AssertionError:
            inv_ok = False
        ia = offered_att(per_class, "interactive")
        row = {
            "p50_ttft_s": m.ttft_percentile(0.5),
            "p99_ttft_s": m.ttft_percentile(0.99),
            "slo_attainment": m.slo_attainment,
            "goodput_tok_s": m.goodput,
            "throughput_tok_s": m.throughput,
            "finished": len(m.requests),
            "shed": m.shed_count,
            "cancelled": len(m.cancelled),
            "expired": len(m.expired),
            "failed": len(m.failed_requests),
            "per_class": per_class,
            "interactive_offered_attainment": ia,
            "brownout_transitions": len(m.brownout_events),
            "brownout_timeline": m.brownout_events,
            "invariants_clean": inv_ok,
        }
        results["grid"][name] = row
        csv.add(f"surge.{name}", wall,
                f"finished={row['finished']}/{n};"
                f"shed={row['shed']};cancelled={row['cancelled']};"
                f"expired={row['expired']};"
                f"interactive_att={'n/a' if ia is None else f'{ia:.3f}'};"
                f"goodput={row['goodput_tok_s']:.1f}tok/s;"
                f"brownout_stages={len(row['brownout_timeline'])}")
    g = results["grid"]
    # survivors of the storm run must commit the exact streams the
    # cancellation-free run committed (intersection of finished ids;
    # brownout cell excluded — its best_effort output cap intentionally
    # clips streams)
    common = set(toks["base"]) & set(toks["no_brownout"])
    fired = {e["to"] for e in g["brownout"]["brownout_timeline"]}
    ia_bo = g["brownout"]["interactive_offered_attainment"]
    ia_nb = g["no_brownout"]["interactive_offered_attainment"]
    results["acceptance"] = {
        "interactive_attainment_improves": (
            ia_bo is not None and ia_nb is not None and ia_bo > ia_nb),
        "goodput_improves": (g["brownout"]["goodput_tok_s"]
                             > g["no_brownout"]["goodput_tok_s"]),
        "all_accounted": all(
            sum(b["offered"] for b in c["per_class"].values()) == n
            for c in g.values()),
        "invariants_clean": all(c["invariants_clean"] for c in g.values()),
        "streams_identical": (len(common) > 0 and all(
            toks["base"][k] == toks["no_brownout"][k] for k in common)),
        "stage_spec_off_fired": "spec_off" in fired,
        "stage_draft_offload_fired": "draft_offload" in fired,
    }
    csv.add("surge.acceptance", 0.0,
            ";".join(f"{k}={v}" for k, v in results["acceptance"].items()))
    with open(bench_out("BENCH_surge.json"), "w") as f:
        json.dump(results, f, indent=1)


def cluster_routers(csv: CSV, fast: bool):
    """Router-policy comparison at moderate load on 2 replicas."""
    for router in ("rr", "jsq", "kv"):
        rate, n = 40, (160 if fast else 400)
        t0 = time.perf_counter()
        m, _ = run_cluster("7b", 2, "nightjar", router=router, rate=rate,
                           n=n, dataset="sharegpt")
        csv.add(f"cluster.router.{router}", (time.perf_counter() - t0) * 1e6,
                f"throughput={m.throughput:.1f}tok/s;"
                f"mean_latency={m.mean_latency:.2f}s;"
                f"balance={'/'.join(map(str, m.replica_counts()))}")


# ---------------------------------------------------------------------------
# Backend grid: dense slot caches vs the paged-KV runtime (REAL execution)
# ---------------------------------------------------------------------------


def backend_grid(csv: CSV, fast: bool):
    """Dense-slot vs paged-KV real backends on actual JAX execution:
    prefill / decode / verify step latency (wall clock, post-compile) and
    the max admissible batch at a fixed HBM KV budget (dense reserves
    max_seq tokens per slot; paged admits by actual context through the
    BlockManager).  Persists the grid to BENCH_backend.json."""
    from repro.serving.kv_cache import BlockManager, OutOfBlocks
    from repro.serving.real_backend import DenseSlotBackend, RealBackend
    from repro.serving.request import Request, Sequence

    cfg = configs.reduced(configs.get_config("deepseek-7b")).replace(
        dtype="float32")
    dcfg = configs.reduced(configs.get_draft_config("deepseek-7b")).replace(
        dtype="float32")
    target, draft = registry.get_model(cfg), registry.get_model(dcfg)

    B = 2 if fast else 4
    P = 16            # prompt tokens
    max_seq = 128     # dense per-slot reservation
    block_size = 8
    rng = np.random.default_rng(0)
    results = {"batch": B, "prompt": P, "max_seq": max_seq,
               "block_size": block_size, "grid": {}}

    def mkseqs(base):
        return [Sequence(request=Request(
            base + i, 0.0, P, 64,
            prompt_tokens=[int(x) for x in rng.integers(0, cfg.vocab_size, P)]))
            for i in range(B)]

    for mode in ("dense", "paged"):
        if mode == "dense":
            be = DenseSlotBackend(target, draft, max_batch=B,
                                  max_seq=max_seq, seed=0)
        else:
            bm = BlockManager(max(B * max_seq // block_size, 64), block_size)
            be = RealBackend(target, draft, max_batch=B, max_seq=max_seq,
                             seed=0, block_manager=bm)
        warm = mkseqs(100)
        be.prefill(warm, with_draft=True)      # compile
        for s in warm:
            be.release(s)
        seqs = mkseqs(0)
        t0 = time.perf_counter()
        be.prefill(seqs, with_draft=True)
        t_pref = time.perf_counter() - t0
        be.step(seqs, 0)                        # compile AR
        be.step(seqs, 2)                        # compile spec
        _, t_dec = timed(lambda: be.step(seqs, 0), repeat=3 if fast else 5)
        _, t_ver = timed(lambda: be.step(seqs, 2), repeat=3 if fast else 5)
        row = {"prefill_s": t_pref, "decode_step_s": t_dec,
               "verify_step_s": t_ver}
        results["grid"][mode] = row
        csv.add(f"backend.{mode}.prefill", t_pref * 1e6,
                f"batch={B};prompt={P}")
        csv.add(f"backend.{mode}.decode", t_dec * 1e6, f"batch={B}")
        csv.add(f"backend.{mode}.verify", t_ver * 1e6, f"batch={B};gamma=2")

    # max admissible batch at a fixed KV budget: the paged pool admits by
    # ACTUAL context (prompt + a 32-token decode horizon) while dense must
    # reserve max_seq tokens per slot up front
    budget_tokens = 2048
    n_dense = budget_tokens // max_seq
    bm = BlockManager(budget_tokens // block_size, block_size)
    n_paged = 0
    try:
        while True:
            bm.allocate(n_paged, P + 1)
            bm.append_tokens(n_paged, 32)
            n_paged += 1
    except OutOfBlocks:
        pass
    results["capacity"] = {"budget_tokens": budget_tokens,
                           "dense_max_batch": n_dense,
                           "paged_max_batch": n_paged}
    csv.add("backend.capacity", 0.0,
            f"budget_tokens={budget_tokens};dense={n_dense};paged={n_paged};"
            f"gain={n_paged / max(n_dense, 1):.1f}x")

    with open(bench_out("BENCH_backend.json"), "w") as f:
        json.dump(results, f, indent=1)


# ---------------------------------------------------------------------------
# Table 3: C_switch profiling (real tier + analytic tier)
# ---------------------------------------------------------------------------


def table3_cswitch(csv: CSV, fast: bool):
    # analytic tier: the paper's 7B/0.5B pair on the 4090 profile
    cm = RooflineCostModel(RTX_4090)
    draft = configs.get_draft_config("paper-7b")
    for delta in (128, 256, 512):
        for batch in ((32, 64) if True else ()):
            c = cm.prefill_latency(draft, batch, delta)
            csv.add(f"table3.analytic.len{delta}.b{batch}", 0.0,
                    f"cswitch={c*1e3:.2f}ms")

    # real tier: wall-clock draft re-prefill of a tiny model on CPU
    dcfg = configs.reduced(configs.get_draft_config("paper-7b"))
    api = registry.get_model(dcfg)
    params = api.init(jax.random.PRNGKey(0))
    prefill = jax.jit(lambda p, b: api.prefill(p, b, 600))

    def measure(delta, batch):
        toks = jnp.zeros((batch, delta), jnp.int32)
        out = prefill(params, {"tokens": toks})
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        out = prefill(params, {"tokens": toks})
        jax.block_until_ready(out)
        return time.perf_counter() - t0

    table = CSwitchTable.profile(measure, deltas=(128, 256, 512),
                                 batches=(2, 8) if fast else (2, 8, 32))
    for (d, b), v in sorted(table.table.items()):
        csv.add(f"table3.real.len{d}.b{b}", v * 1e6, f"cswitch={v*1e3:.2f}ms")


# ---------------------------------------------------------------------------
# Table 7: elastic memory operation overheads (real execution)
# ---------------------------------------------------------------------------


def table7_memops(csv: CSV, fast: bool):
    from repro.serving.kv_cache import BlockManager, PhysicalKVPool
    L, nb, bs, kh, hd = 8, 256, 16, 8, 64
    pool = PhysicalKVPool(L, nb, bs, kh, hd)
    bm = BlockManager(nb, bs)
    bm.allocate(1, nb * bs - bs)

    # expansion: attach blocks (pool grow + free-list update)
    def expand():
        p2 = PhysicalKVPool(L, nb, bs, kh, hd)
        p2.grow(32)
        return p2
    _, dt = timed(expand, repeat=2)
    csv.add("table7.expansion", dt * 1e6, f"latency={dt*1e3:.1f}ms")

    # contraction: kernel-backed block migration of 32 blocks
    src = jnp.arange(nb - 32, nb, dtype=jnp.int32)
    dst = jnp.arange(0, 32, dtype=jnp.int32)

    def contract():
        from repro.kernels import ops
        pool.k = ops.migrate_blocks(pool.k, src, dst).block_until_ready()
        return pool.k
    _, dt = timed(contract, repeat=3)
    csv.add("table7.contraction.vectorized", dt * 1e6,
            f"latency={dt*1e3:.2f}ms;blocks=32")

    # reload dispatch: CPU overhead of triggering the async reload
    from repro.serving.memory_manager import ElasticMemoryManager
    bm2 = BlockManager(100, 4)
    mm = ElasticMemoryManager(bm2, draft_blocks=10, t_persist=1)
    mm.draft_resident = False
    mm.expanded = True
    bm2.expand(10)
    t0 = time.perf_counter()
    mm.step(0.0, spec_disabled=True, waiting=0)
    dt = time.perf_counter() - t0
    csv.add("table7.reload_dispatch", dt * 1e6, f"latency={dt*1e6:.1f}us")


# ---------------------------------------------------------------------------
# Appendix A: sublinear regret
# ---------------------------------------------------------------------------


def appendix_regret(csv: CSV, fast: bool):
    lat = {0: 0.030, 1: 0.022, 2: 0.016, 3: 0.018, 4: 0.021, 5: 0.025}
    best = min(lat.values())
    horizons = [2000, 8000] if fast else [2000, 8000, 32000]
    Rs = []
    for T in horizons:
        pl = NightjarPlanner(5, seed=0)
        rng = np.random.default_rng(1)
        R = 0.0
        for t in range(T):
            g = pl.select(8)
            pl.observe(8, g, max(lat[g] + rng.normal(0, 0.002), 1e-6))
            R += lat[g] - best
        Rs.append(R)
        csv.add(f"regret.T{T}", 0.0,
                f"R={R:.2f};R_over_sqrtT={R/math.sqrt(T):.4f};"
                f"switches={pl.switch_count}")
    # sublinearity: R(4T)/R(T) should be well under 4 (≈2 for sqrt)
    ratio = Rs[-1] / Rs[0]
    growth = horizons[-1] / horizons[0]
    csv.add("regret.sublinearity", 0.0,
            f"R_ratio={ratio:.2f};T_ratio={growth};sublinear={ratio < growth}")


# ---------------------------------------------------------------------------
# Kernel microbenchmarks
# ---------------------------------------------------------------------------


def kernel_microbench(csv: CSV, fast: bool):
    from repro.kernels import ops
    key = jax.random.PRNGKey(0)

    # block migration (the pool is donated: each call migrates the last
    # result back and forth)
    x = jax.random.normal(key, (8, 512, 16, 8, 64), jnp.float32)
    src = np.arange(480, 512, dtype=np.int32)
    dst = np.arange(0, 32, dtype=np.int32)

    def migrate():
        nonlocal x
        x = ops.migrate_blocks(x, src, dst).block_until_ready()
        return x
    _, dt = timed(migrate, repeat=3)
    csv.add("kernel.block_migration.32x1MB", dt * 1e6,
            f"GBps={(32*8*16*8*64*4*2/dt)/1e9:.1f}")

    B, H, KH, D, bs, maxb = 8, 16, 4, 128, 16, 16
    q = jax.random.normal(key, (B, H, D))
    kp = jax.random.normal(key, (1, 256, bs, KH * D))   # one-layer pool
    vp = jax.random.normal(key, (1, 256, bs, KH * D))
    tables = jax.random.randint(key, (B, maxb), 0, 256)
    lengths = jnp.full((B,), maxb * bs)
    _, dt = timed(lambda: ops.paged_attention(
        q, kp, vp, 0, tables, lengths).block_until_ready(), repeat=5)
    csv.add("kernel.paged_attention.b8h16", dt * 1e6,
            f"ctx={maxb*bs}")

    # multi-query extension (speculative verify / chunked-prefill appends)
    qm = jax.random.normal(key, (B, 4, H, D))
    _, dt = timed(lambda: ops.paged_attention(
        qm, kp, vp, 0, tables, lengths).block_until_ready(), repeat=5)
    csv.add("kernel.paged_attention.b8t4h16", dt * 1e6,
            f"ctx={maxb*bs};T=4")

    S = 512 if fast else 1024
    q = jax.random.normal(key, (2, S, 8, 64), jnp.float32)
    k = jax.random.normal(key, (2, S, 8, 64), jnp.float32)
    _, dt = timed(lambda: ops.flash_attention(
        q, k, k, causal=True).block_until_ready(), repeat=3)
    csv.add(f"kernel.flash_attention.s{S}", dt * 1e6,
            f"gflops={(4*2*8*S*S*64/2/dt)/1e9:.1f}")


# ---------------------------------------------------------------------------
# Roofline table (reads the dry-run artifacts)
# ---------------------------------------------------------------------------


def roofline(csv: CSV, fast: bool):
    for fname in ("dryrun_single_pod.json", "dryrun_multi_pod.json"):
        path = os.path.join(os.path.dirname(__file__), "..", fname)
        if not os.path.exists(path):
            csv.add(f"roofline.{fname}", 0.0, "missing=run dryrun first")
            continue
        cells = json.load(open(path))
        for c in cells:
            csv.add(
                f"roofline.{c['mesh']}.{c['arch']}.{c['shape']}", 0.0,
                f"bottleneck={c['bottleneck']};"
                f"compute_s={c['compute_s']:.4f};"
                f"memory_s={c['memory_s']:.4f};"
                f"collective_s={c['collective_s']:.4f};"
                f"peak_gb={c['peak_bytes_per_device']/1e9:.2f};"
                f"fits={c['fits_hbm']}")


BENCHES = {
    "fig2": fig2_fixed_gamma,
    "table5": table5_table6,
    "fig9": fig9_low_high,
    "fig11": fig11_dynamic_trace,
    "fig12": fig12_bandit_ablation,
    "fig13": fig13_offload,
    "fig14": fig14_threshold,
    "fig15": fig15_fixed_vs_adaptive,
    "prefill": prefill_hybrid,
    "prefix": prefix_grid,
    "sessions": sessions_grid,
    "backend": backend_grid,
    "cluster": cluster_sweep,
    "routers": cluster_routers,
    "control": control_grid,
    "disagg": disagg_grid,
    "chaos": chaos_grid,
    "surge": surge_grid,
    "table3": table3_cswitch,
    "table7": table7_memops,
    "regret": appendix_regret,
    "kernels": kernel_microbench,
    "roofline": roofline,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--fast", action="store_true")
    args = ap.parse_args()
    csv = CSV()
    print("name,us_per_call,derived")
    for name, fn in BENCHES.items():
        if args.only and args.only != name:
            continue
        t0 = time.perf_counter()
        fn(csv, args.fast)
        print(f"# {name} done in {time.perf_counter()-t0:.1f}s",
              file=sys.stderr)


if __name__ == "__main__":
    main()
