#!/usr/bin/env python3
"""Smoke run of the real serving path on one TPU v5e chip.

Serves the paper's 7B target/draft pair at its published widths
(``paper-7b-1chip``: the target cut to 14 of its 28 layers, the draft
whole) through the entry points a deployment uses: ``build_real_engine``
-> RealBackend over the paged KV pools, the continuous-batching scheduler,
the nightjar planner and the elastic memory manager.  Weights are random,
drawn from a seed.  Phases, each reported on its own line:

  (a) device, configuration, weight bytes, pool size, device memory in use;
  (b) 8 seeded requests (prompts 64..1536 tokens, 64 output tokens each)
      served to completion; gamma histogram and acceptance rate;
  (c) one forced elastic cycle: draft offload, pool growth, serving in the
      grown pool, contraction with block migration (migrated blocks checked
      byte for byte against their sources), draft reload;
  (d) paged prefill + 8 decode steps against the dense full forward, same
      bf16 weights, matmuls at "highest" precision;
  (e) compile and wall seconds per phase (smoke figures, not metrics).

Usage:
  python chip_smoke.py               # one TPU chip; anything else fails
  python chip_smoke.py --rehearse    # CPU rehearsal: reduced widths, short
                                     # prompts, interpret-mode kernels

The last line of a run that passed every phase is
  {"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}
A failed phase raises: the exit code is non-zero and no such line appears.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

ARCH = "paper-7b-1chip"
SEED = 0

# Published peaks of one chip, keyed by the device_kind string the chip
# reports (Google Cloud documentation, "TPU v5e").  A kind not listed here
# is an error, never a default.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes": 16e9,
                    "hbm_bytes_per_s": 819e9},
}

# (b)-(d) workload per mode: prompt lengths of phase (b) cross the 1024
# bucket on the chip (64 in the rehearsal); outputs are 64 tokens
SIZES = {
    "chip": dict(prompts=(64, 256, 512, 768, 1000, 1100, 1280, 1536),
                 output=64, cycle_prompts=(128, 200, 300, 400),
                 cycle_output=48, check_prompts=(200, 333)),
    "rehearse": dict(prompts=(8, 16, 24, 40, 48, 64, 80, 96), output=16,
                     cycle_prompts=(10, 20, 30, 40), cycle_output=40,
                     check_prompts=(20, 33)),
}
CHECK_DECODE_STEPS = 8


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def report(phase: str, **fields) -> None:
    print(f"[{phase}] " + json.dumps(fields, sort_keys=False), flush=True)


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling while the clock is
    entered, read from its monitoring events."""

    def __init__(self, jax):
        self.monitoring = jax.monitoring
        self.total = 0.0

    def _on_event(self, event: str, secs: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.total += secs

    def __enter__(self):
        self.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        self.monitoring.unregister_event_duration_listener(self._on_event)


def memory_stat(dev, key: str = "bytes_in_use"):
    """A device memory counter; None where the backend keeps none (CPU)."""
    stats = dev.memory_stats()
    return None if stats is None else int(stats[key])


def tree_bytes(jax, tree) -> int:
    return int(sum(x.nbytes for x in jax.tree.leaves(tree)))


def requests(sizes, output, *, first_id, arrival, vocab, seed):
    import numpy as np

    from repro.serving.request import Request
    rng = np.random.default_rng(seed)
    return [Request(first_id + i, arrival, n, output,
                    prompt_tokens=rng.integers(0, vocab, n).tolist())
            for i, n in enumerate(sizes)]


def phase_setup(jax, dev, rehearse: bool):
    from repro.serving.real_backend import build_real_engine
    engine = build_real_engine(ARCH, "nightjar", reduced=rehearse,
                               max_batch=8, gamma_max=5, seed=SEED)
    be = engine.backend
    jax.block_until_ready((be.tparams, be.dparams, be.tkv.pages,
                           be.dkv.pages))
    t, d = be.target.cfg, be.draft.cfg
    widths = lambda c: dict(layers=c.num_layers, d_model=c.d_model,
                            heads=c.num_heads, kv_heads=c.num_kv_heads,
                            head_dim=c.resolved_head_dim, d_ff=c.d_ff,
                            vocab=c.vocab_size, qkv_bias=c.qkv_bias)
    report("a", device=dev.device_kind, platform=dev.platform, config=t.name,
           target=widths(t), draft=widths(d),
           target_weight_bytes=tree_bytes(jax, be.tparams),
           draft_weight_bytes=tree_bytes(jax, be.dparams),
           pool_blocks=be.bm.total_blocks, block_size=be.bm.block_size,
           kv_bytes_per_block=be.tkv.bytes_per_block
           + be.dkv.bytes_per_block,
           bytes_in_use=memory_stat(dev),
           bytes_limit=memory_stat(dev, "bytes_limit"))
    return engine


def phase_serve(engine, size):
    be = engine.backend
    reqs = requests(size["prompts"], size["output"], first_id=0, arrival=0.0,
                    vocab=be.target.cfg.vocab_size, seed=SEED + 1)
    m = engine.run(reqs, max_steps=10 * size["output"], record_timeline=True)
    done = {r.req_id for r in m.requests}
    check(done == {r.req_id for r in reqs},
          f"(b) requests unfinished: {sorted({r.req_id for r in reqs} - done)}")
    for r in reqs:
        check(len(be.output_tokens(r.req_id)) >= r.output_len,
              f"(b) request {r.req_id} committed too few tokens")
    steps = [s for s in m.timeline if s["B"] > 0]
    hist = {}
    for s in steps:
        hist[s["gamma"]] = hist.get(s["gamma"], 0) + 1
    spec = [s for s in steps if s["gamma"] > 0]
    proposed = sum(s["B"] * s["gamma"] for s in spec)
    accepted = sum(s["tokens"] - s["B"] for s in spec)
    check(bool(spec), "(b) no speculative step (gamma >= 1) ran")
    check(hist.get(0, 0) > 0, "(b) no plain decode step (gamma = 0) ran")
    report("b", requests=len(reqs), finished=len(done),
           prompt_tokens=list(size["prompts"]),
           output_tokens=m.total_tokens, steps=len(steps),
           gamma_histogram={str(g): n for g, n in sorted(hist.items())},
           acceptance_rate=accepted / proposed)


def phase_elastic(jax, dev, engine, size):
    be, mm = engine.backend, engine.memmgr
    base_blocks = be.bm.total_blocks
    seen = {"compared_bytes": 0}
    offload, migrate = mm.offload_fn, mm.migrate_fn

    def offload_and_measure():
        seen["before"] = memory_stat(dev)
        offload()
        seen["after"] = memory_stat(dev)

    def migrate_and_compare(plan):
        """Read the plan's source blocks of every page array (an XLA
        gather, independent of the kernel) before the migration, and its
        destination blocks after it: they must match byte for byte."""
        pools = {"target": be.tkv, "draft": be.dkv}
        before = {n: kv.spill_blocks(plan.src) for n, kv in pools.items()}
        secs = migrate(plan)
        for n, kv in pools.items():
            after = kv.spill_blocks(plan.dst)
            for key, src in before[n].items():
                # the draft, offloaded while the grown blocks were written,
                # left its pages of them zero: only the target's hold KV
                check(n == "draft" or src.any(),
                      f"(c) {n} {key}: source blocks are empty")
                check(src.tobytes() == after[key].tobytes(),
                      f"(c) {n} {key}: migrated blocks differ from their "
                      "sources")
                seen["compared_bytes"] += src.nbytes
        return secs
    mm.offload_fn, mm.migrate_fn = offload_and_measure, migrate_and_compare

    reqs = requests(size["cycle_prompts"], size["cycle_output"],
                    first_id=100, arrival=engine.clock,
                    vocab=be.target.cfg.vocab_size, seed=SEED + 2)
    for r in reqs:
        engine.submit(r)
    mm.force_offload = True
    grown_steps = 0
    for _ in range(10 * size["cycle_output"]):
        engine.step()
        if mm.expanded:
            grown_steps += 1
        if grown_steps == size["cycle_output"] // 2:
            break
    check(mm.expanded and not be.draft_resident,
          "(c) the draft was not offloaded / the pool did not grow")
    grown_blocks = be.bm.total_blocks
    high = sum(b >= base_blocks for t in be.bm.tables.values() for b in t)
    check(high > 0, "(c) nothing was served from the grown pool")
    mm.force_offload = False
    while engine.step() is not None:
        pass
    mm.offload_fn, mm.migrate_fn = offload, migrate
    done = {r.req_id for r in engine.metrics.requests}
    check({r.req_id for r in reqs} <= done, "(c) requests unfinished")
    contract = [e for e in mm.events if e.kind == "contract"]
    check(bool(contract), "(c) the pool never contracted")
    migrated = contract[-1].detail["migrated_blocks"]
    check(migrated > 0, "(c) contraction migrated no blocks")
    check(seen["compared_bytes"] > 0,
          "(c) no migrated block was compared with its source")
    check(be.draft_resident and be.bm.total_blocks == base_blocks,
          "(c) the draft was not reloaded / the pool did not shrink")
    freed = None
    if seen["before"] is not None:
        freed = seen["before"] - seen["after"]
        check(freed >= 0.9 * tree_bytes(jax, be.dparams),
              f"(c) offload freed {freed} bytes, less than the draft's")
    from repro.kernels import ops
    report("c", base_blocks=base_blocks, grown_blocks=grown_blocks,
           grown_pool_steps=grown_steps, blocks_above_boundary=high,
           bytes_in_use_before_offload=seen["before"],
           bytes_in_use_after_offload=seen["after"], bytes_freed=freed,
           migrated_blocks=migrated, migration_seconds=contract[-1].latency,
           migration_kernel=ops.kernel_mode(),
           migrated_bytes_equal_to_sources=seen["compared_bytes"],
           requests_finished=len(reqs))


def phase_logits(jax, engine, size):
    """Paged prefill + CHECK_DECODE_STEPS decode steps through the cache
    against the dense full forward of the same bf16 weights."""
    import jax.numpy as jnp
    import numpy as np

    from repro.models import transformer
    be = engine.backend
    api, cfg, params = be.target, be.target.cfg, be.tparams
    P = np.asarray(size["check_prompts"], np.int32)
    n = CHECK_DECODE_STEPS
    S = int(P.max()) + n
    rng = np.random.default_rng(SEED + 3)
    seqs = rng.integers(0, cfg.vocab_size, (len(P), S)).astype(np.int32)
    bs = be.bm.block_size
    per_seq = -(-S // bs)
    tables = np.arange(len(P) * per_seq, dtype=np.int32).reshape(len(P), -1)

    prefill = jax.jit(lambda p, pg, tok, tb, st, v: api.decode_step_paged(
        p, pg, tok, tb, st, v, last_only=True))
    decode = jax.jit(api.decode_step_paged)
    dense = jax.jit(lambda p, tok: transformer.unembed(
        cfg, p, api.forward(p, {"tokens": tok})))
    with jax.default_matmul_precision("highest"):
        pages = api.init_paged_cache(len(P) * per_seq, bs)
        Pmax = int(P.max())
        prompt = np.where(np.arange(Pmax)[None] < P[:, None], seqs[:, :Pmax], 0)
        lg, pages = prefill(params, pages, prompt, tables,
                            np.zeros(len(P), np.int32), P)
        paged = [lg[:, 0]]
        for j in range(n):
            tok = seqs[np.arange(len(P)), P + j][:, None]
            lg, pages = decode(params, pages, tok, tables, P + j)
            paged.append(lg[:, 0])
        paged = np.stack([np.asarray(x, np.float32) for x in paged], 1)
        full = np.asarray(dense(params, seqs), np.float32)   # (B, S, V)
    idx = P[:, None] - 1 + np.arange(n + 1)[None]
    ref = np.take_along_axis(full, idx[:, :, None], axis=1)
    err = float(np.abs(paged - ref).max())
    scale = float(np.abs(ref).max())
    # bf16 keeps 8 significant bits: the two paths round different
    # intermediates (paged kernel vs blockwise attention order), so each
    # layer may move the residual stream by about one bf16 ulp (2^-8
    # relative) and the embedding/unembedding adds one more; the logit
    # error is bounded by (layers + 1) * 2^-8 of the logit scale
    tol = (cfg.num_layers + 1) * 2.0 ** -8 * scale
    agree = float((paged.argmax(-1) == ref.argmax(-1)).mean())
    check(np.isfinite(paged).all(), "(d) non-finite paged logits")
    check(err <= tol, f"(d) max |logit error| {err} exceeds tolerance {tol}")
    report("d", prompts=P.tolist(), decode_steps=n, positions=int(idx.size),
           max_abs_error=err, tolerance=tol, logit_scale=scale,
           argmax_agreement=agree,
           tolerance_reason="(layers + 1) bf16 roundings (2^-8 relative) "
                            "of the logit scale: the paged and dense paths "
                            "round different intermediates in bf16")


def run(rehearse: bool) -> dict:
    import jax

    from repro.kernels import ops
    dev = jax.devices()[0]
    if rehearse:
        check(dev.platform == "cpu", "--rehearse runs on the CPU")
        peaks = None
    else:
        check(dev.platform == "tpu",
              f"no TPU: the first device is {dev.platform} "
              f"({dev.device_kind}); use --rehearse for a CPU rehearsal")
        check(len(jax.devices()) == 1,
              f"expected one chip, found {len(jax.devices())}")
        check(dev.device_kind in PEAKS,
              f"no peaks listed for device kind {dev.device_kind!r}")
        peaks = PEAKS[dev.device_kind]
    size = SIZES["rehearse" if rehearse else "chip"]
    timings = {}
    with CompileClock(jax) as clock:
        def timed(name, fn, *args):
            c0, t0 = clock.total, time.perf_counter()
            out = fn(*args)
            timings[name] = {"wall_s": time.perf_counter() - t0,
                             "compile_s": clock.total - c0,
                             "peak_bytes_in_use": memory_stat(
                                 dev, "peak_bytes_in_use")}
            return out

        engine = timed("a", phase_setup, jax, dev, rehearse)
        if peaks is not None:
            used = memory_stat(dev)
            check(used < peaks["hbm_bytes"],
                  f"(a) {used} bytes in use exceed the chip's HBM")
        timed("b", phase_serve, engine, size)
        timed("c", phase_elastic, jax, dev, engine, size)
        timed("d", phase_logits, jax, engine, size)
    report("e", label="smoke figures, not metrics", kernels=ops.kernel_mode(),
           peaks=peaks, phases=timings)
    return {"ok": True, "device": {"platform": dev.platform,
                                   "kind": dev.device_kind,
                                   "count": len(jax.devices())}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="run every phase on the CPU at the reduced size "
                         "with interpret-mode kernels")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: the repository's sources are missing ({SRC})",
              file=sys.stderr)
        return 2
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
    from repro.kernels import ops
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.rehearse:
        with ops.interpret_kernels():
            result = run(rehearse=True)
    else:
        result = run(rehearse=False)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
