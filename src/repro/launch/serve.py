"""Serving entrypoint.

Real execution tier (actual JAX compute over the paged-KV runtime, the
configuration as registered; --reduced swaps in tiny widths for a CPU run;
--chunk-tokens also works here — chunked prefill runs on real execution
through RealBackend.hybrid_step):
  PYTHONPATH=src python -m repro.launch.serve --tier real --reduced \
      --arch paper-7b --max-batch 4 --requests 8
  (on one TPU v5e: --arch paper-7b-1chip, without --reduced)

Analytical paper-scale tier (TPU v5e cost model):
  PYTHONPATH=src python -m repro.launch.serve --arch paper-7b --tier sim \
      --rate 20 --requests 300 --policy nightjar

Multi-replica cluster on the simulated tier (per-replica planners behind a
router; --rate is the TOTAL arrival rate across the fleet):
  PYTHONPATH=src python -m repro.launch.serve --arch paper-7b --tier sim \
      --replicas 4 --router jsq --rate 80 --requests 800

Chunked-prefill hybrid batching with a 0.5s TTFT SLO (tail-latency regime):
  PYTHONPATH=src python -m repro.launch.serve --arch paper-7b --tier sim \
      --rate 30 --requests 600 --chunk-tokens 256 --slo 0.5

Prefix-sharing copy-on-write KV caching on the templated workload (shared
system prompt; cached prefixes cost no prefill compute and no new blocks):
  PYTHONPATH=src python -m repro.launch.serve --arch paper-7b --tier sim \
      --dataset templated --rate 60 --chunk-tokens 384 --prefix-caching on

Cluster control plane: sticky prefix-affinity routing over a multi-template
workload (each replica's cache specialises on its own templates):
  PYTHONPATH=src python -m repro.launch.serve --arch paper-7b --tier sim \
      --replicas 2 --router affinity --dataset templated --num-templates 8 \
      --chunk-tokens 384 --prefix-caching on --rate 60

Elastic autoscaling + admission control on a bursty trace (the fleet starts
at 1 replica, grows to --replicas under the spike, sheds hopeless arrivals
at the door, drains back down after):
  PYTHONPATH=src python -m repro.launch.serve --arch paper-7b --tier sim \
      --replicas 2 --router slo --autoscale --shed-factor 1.5 \
      --dataset alpaca --bursty --requests 400

Host-memory KV offload tier on the multi-turn session workload (evicted
prefix blocks spill to a host-side store and restore on the next turn's
prefix hit instead of re-running prefill):
  PYTHONPATH=src python -m repro.launch.serve --arch paper-7b --tier sim \
      --dataset sessions --requests 48 --rate 0.5 --chunk-tokens 384 \
      --prefix-caching on --kv-offload

Disaggregated prefill/decode fleet on the mixed long-prompt/long-decode
workload (arrivals prefill on a chunked-prefill pool, then the priced KV
handoff migrates each finished prompt's blocks to a decode replica — or
keeps it local when the transfer would lose):
  PYTHONPATH=src python -m repro.launch.serve --arch paper-7b --tier sim \
      --hw rtx-4090 --dataset mixed --rate 28 --requests 500 \
      --chunk-tokens 128 --max-batch 48 --disaggregate 2:2
(the 24GB 4090 profile: the mixed workload's long prompts need KV headroom
beside the 7B weights, which the 16GB v5e profile does not have)
"""
from __future__ import annotations

import argparse
import json


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper-7b")
    ap.add_argument("--policy", default="nightjar")
    ap.add_argument("--tier", choices=["real", "sim"], default="sim")
    ap.add_argument("--reduced", action="store_true",
                    help="real tier: run configs.reduced of the pair (tiny "
                         "widths, for the CPU) instead of the registered "
                         "configuration")
    ap.add_argument("--rate", type=float, default=10.0)
    ap.add_argument("--requests", type=int, default=200)
    ap.add_argument("--dataset", default="sharegpt")
    ap.add_argument("--qa-frac", type=float, default=None,
                    help="document-QA fraction of the mixed long-prompt/"
                         "long-decode dataset (default: the dataset's "
                         "tuned 0.25; only valid with --dataset mixed)")
    ap.add_argument("--gamma-max", type=int, default=5)
    ap.add_argument("--max-batch", type=int, default=256)
    ap.add_argument("--chunk-tokens", default="0",
                    help="per-step prefill token budget for chunked-prefill "
                         "hybrid batching (0 = monolithic; 'auto' derives "
                         "the budget from the roofline compute-bound knee, "
                         "falling back to 256 without a cost model)")
    ap.add_argument("--slo", type=float, default=None,
                    help="TTFT deadline in seconds for SLO-attainment/"
                         "goodput (default: per-dataset; <=0 disables)")
    ap.add_argument("--prefix-caching", choices=["on", "off"], default="off",
                    help="vLLM-style copy-on-write prefix sharing: cached "
                         "prompt prefixes are admitted at refcount+1 and "
                         "skip prefill compute (chunked scheduler path)")
    ap.add_argument("--prefill-order", choices=["fifo", "slo"],
                    default="fifo",
                    help="waiting-queue admission order for chunked "
                         "prefill: FIFO or earliest-TTFT-deadline first")
    ap.add_argument("--kv-offload", action="store_true",
                    help="host-memory KV spill tier: evicted cached-prefix "
                         "blocks move to a host store and restore into free "
                         "device blocks on a later prefix hit (requires "
                         "--prefix-caching on)")
    ap.add_argument("--no-offload", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--replicas", type=int, default=1,
                    help="sim tier only: number of engine replicas (with "
                         "--autoscale this is the fleet's max size)")
    ap.add_argument("--router", default="jsq",
                    choices=["rr", "jsq", "kv", "slo", "affinity"],
                    help="dispatch policy for --replicas > 1: rr/jsq/kv "
                         "(load / headroom), slo (predicted-TTFT deadline "
                         "headroom), affinity (sticky template-hash routing "
                         "with load-aware spillover)")
    ap.add_argument("--autoscale", action="store_true",
                    help="elastic fleet: start at 1 replica, scale up to "
                         "--replicas on a windowed SLO-attainment signal, "
                         "drain back down when load clears")
    ap.add_argument("--shed-factor", type=float, default=0.0,
                    help="admission control: shed an arrival when every "
                         "replica's predicted TTFT exceeds slo * factor "
                         "(0 disables)")
    ap.add_argument("--num-templates", type=int, default=1,
                    help="templated dataset: number of distinct system-"
                         "prompt templates (affinity-routing workload)")
    ap.add_argument("--bursty", action="store_true",
                    help="sim tier: draw arrivals from the bursty "
                         "baseline->spike->drain trace instead of a "
                         "constant-rate Poisson process")
    ap.add_argument("--hw", default="tpu-v5e",
                    choices=["tpu-v5e", "rtx-4090", "a100-40g"],
                    help="sim tier: roofline hardware profile")
    ap.add_argument("--disaggregate", default=None, metavar="P:D",
                    help="sim tier: split the fleet into P prefill + D "
                         "decode replicas with priced KV handoff (requires "
                         "--chunk-tokens > 0; overrides --replicas)")
    ap.add_argument("--handoff-margin", type=float, default=0.0,
                    help="pricer hysteresis in seconds: a handoff must beat "
                         "staying put by at least this much")
    ap.add_argument("--fault-plan", default=None, metavar="SPEC",
                    help="sim tier: deterministic fault schedule, e.g. "
                         "'crash:1@2.0;straggle:0@1.0..3.0x4;"
                         "handoff:fail@0..5#2;corrupt:0@4.0#1' — seeded by "
                         "--seed, so the same spec + seed replays the exact "
                         "same faults (forces the cluster path)")
    ap.add_argument("--brownout", action="store_true",
                    help="sim tier: arm the fleet brownout ladder "
                         "(speculation off -> draft offload -> best_effort "
                         "output cap -> class-ordered shedding, with "
                         "hysteresis and cooldowns; forces the cluster "
                         "path; pairs naturally with --shed-factor)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="flight recorder: write a structured trace of the "
                         "run (request lifecycle spans, engine steps, fleet "
                         "events) to PATH.  Sim tier: on the virtual clock, "
                         "deterministic, so same seed => byte-identical file "
                         "(analyse with benchmarks/trace_report.py).  Real "
                         "tier: on the recorder's wall clock, with the "
                         "nightjar.* host spans (admit, plan, reserve, "
                         "prefill/decode, prepare, device_wait, readback, "
                         "commit), which also go into a running "
                         "jax.profiler trace; its nightjar.clock span maps "
                         "the file onto the profiler's timeline")
    ap.add_argument("--trace-format", choices=["jsonl", "chrome"],
                    default="jsonl",
                    help="trace file format: jsonl (trace_report.py input) "
                         "or chrome (Perfetto / chrome://tracing viewable)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the metrics registry in Prometheus text "
                         "exposition format to PATH at end of run")
    args = ap.parse_args()

    if args.kv_offload and args.prefix_caching != "on":
        ap.error("--kv-offload requires --prefix-caching on (the host tier "
                 "is keyed by prefix chain hashes)")
    if args.fault_plan is not None and args.tier != "sim":
        ap.error("--fault-plan runs on the simulated tier only (the "
                 "injector is driven by the shared virtual clock)")
    if args.brownout and args.tier != "sim":
        ap.error("--brownout runs on the simulated tier only (the ladder "
                 "is driven by the shared virtual clock)")
    if args.reduced and args.tier != "real":
        ap.error("--reduced applies to --tier real only")

    from .. import configs
    from .compile_cache import enable_compile_cache
    enable_compile_cache()

    trace_rec = None
    if args.trace or args.metrics_out:
        from ..serving.observability import TraceRecorder
        trace_rec = TraceRecorder(wall=args.tier == "real")

    if args.tier == "sim":
        from ..serving.costmodel import (A100_40G, RTX_4090,
                                         RooflineCostModel, TPU_V5E)
        from ..serving.simulator import (SimConfig, build_sim_cluster,
                                         build_sim_engine)
        from ..serving.workload import (bursty_trace, mixed_requests,
                                        poisson_requests, session_requests,
                                        templated_requests)

        hw = {"tpu-v5e": TPU_V5E, "rtx-4090": RTX_4090,
              "a100-40g": A100_40G}[args.hw]
        target = configs.get_config(args.arch)
        chunk = RooflineCostModel(hw).resolve_chunk_tokens(
            args.chunk_tokens, target)
        cfg = SimConfig(
            target=target,
            draft=configs.get_draft_config(args.arch),
            hw=hw, gamma_max=args.gamma_max, max_batch=args.max_batch,
            chunk_tokens=chunk,
            prefix_caching=args.prefix_caching == "on",
            prefill_order=args.prefill_order,
            enable_offload=not args.no_offload,
            kv_offload=args.kv_offload, seed=args.seed)
        if args.dataset in ("templated", "sessions") and args.bursty:
            ap.error(f"--bursty is not supported with --dataset "
                     f"{args.dataset} (that workload generates its own "
                     f"arrival process); pick one")
        if args.dataset == "sessions":
            # --requests is the TOTAL request budget; each session
            # contributes `turns` requests (one per conversation turn)
            from ..serving.workload import DATASETS
            turns = DATASETS["sessions"]["turns"]
            reqs = session_requests(max(args.requests // turns, 1),
                                    rate_qps=args.rate, seed=args.seed + 1,
                                    slo=args.slo)
        elif args.dataset == "templated":
            # prompts carry real token ids (shared template + suffix) so
            # the prefix cache has content to hash and the affinity router
            # has an identity to be sticky about
            reqs = templated_requests(args.rate, args.requests,
                                      num_templates=args.num_templates,
                                      seed=args.seed + 1, slo=args.slo)
        elif args.dataset == "mixed":
            if args.bursty:
                ap.error("--bursty is not supported with --dataset mixed")
            reqs = mixed_requests(args.rate, args.requests,
                                  qa_frac=args.qa_frac,
                                  seed=args.seed + 1, slo=args.slo)
        elif args.bursty:
            trace = bursty_trace(seed=args.seed)
            reqs = trace.sample_requests(args.requests, dataset=args.dataset,
                                         seed=args.seed + 1, slo=args.slo)
        else:
            reqs = poisson_requests(args.rate, args.requests,
                                    dataset=args.dataset, seed=args.seed + 1,
                                    slo=args.slo)
        if args.qa_frac is not None and args.dataset != "mixed":
            ap.error("--qa-frac only applies to --dataset mixed")
        disaggregate = None
        if args.disaggregate is not None:
            try:
                p, d = (int(x) for x in args.disaggregate.split(":"))
            except ValueError:
                ap.error("--disaggregate takes P:D (e.g. 2:2)")
            if p < 1 or d < 1:
                ap.error("--disaggregate needs at least one replica per pool")
            if chunk <= 0:
                ap.error("--disaggregate requires --chunk-tokens > 0 "
                         "(the prefill pool runs chunked prefill)")
            disaggregate = dict(prefill=p, decode=d,
                                margin_s=args.handoff_margin)
        fault_plan = None
        if args.fault_plan is not None:
            from ..serving.faults import FaultPlan
            try:
                fault_plan = FaultPlan.parse(args.fault_plan)
            except ValueError as e:
                ap.error(f"--fault-plan: {e}")
        brownout = None
        if args.brownout:
            brownout = dict(slo=args.slo if args.slo else 1.0)
        if (args.replicas > 1 or args.autoscale or args.shed_factor > 0
                or disaggregate is not None or fault_plan is not None
                or brownout is not None):
            autoscale = (dict(min_replicas=1, max_replicas=args.replicas)
                         if args.autoscale else None)
            cluster = build_sim_cluster(
                cfg, args.replicas, args.policy, router=args.router,
                shed_factor=args.shed_factor or None, autoscale=autoscale,
                disaggregate=disaggregate, fault_plan=fault_plan,
                brownout=brownout, trace=trace_rec)
            metrics = cluster.run(reqs)
        else:
            engine = build_sim_engine(cfg, args.policy, trace=trace_rec)
            metrics = engine.run(reqs)
    else:
        from ..serving.real_backend import build_real_engine
        from ..serving.workload import tiny_requests

        # the real tier has no wall-clock cost model: 'auto' falls back
        chunk = None
        if args.chunk_tokens != "0":
            chunk = 256 if args.chunk_tokens == "auto" else int(args.chunk_tokens)
        engine = build_real_engine(
            args.arch, args.policy, reduced=args.reduced,
            max_batch=args.max_batch, gamma_max=args.gamma_max,
            chunk_tokens=chunk, prefix_caching=args.prefix_caching == "on",
            prefill_order=args.prefill_order, kv_offload=args.kv_offload,
            offload=not args.no_offload, seed=args.seed)
        if trace_rec is not None:
            engine.attach_trace(trace_rec)
        reqs = tiny_requests(min(args.requests, 16), rate_qps=args.rate,
                             prompt_len=16, output_len=16,
                             vocab=engine.backend.target.cfg.vocab_size,
                             seed=args.seed,
                             template_len=(8 if args.dataset == "templated"
                                           else 0))
        metrics = engine.run(reqs, max_steps=5000)

    if trace_rec is not None:
        if args.trace:
            trace_rec.export(args.trace, fmt=args.trace_format)
        if args.metrics_out:
            with open(args.metrics_out, "w", encoding="utf-8",
                      newline="\n") as f:
                f.write(trace_rec.registry.exposition())

    print(json.dumps(metrics.summary(), indent=1))


if __name__ == "__main__":
    main()
