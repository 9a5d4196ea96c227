"""Tests of the chip benchmark's harness, on the CPU.  Run by path:

  JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests

They cover the CPU rehearsal of every cell (the last line's keys), the
trace reduction on a small recorded trace, the percentile and spread
arithmetic, the decision on the served-token check, the operation and byte
counts at both configurations' widths, the traffic generator, the refusal
of a run without a TPU or on an unknown device kind, and runs whose timed
path is broken underneath, which must come out not correct."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
CHECKOUT = CHIP.parents[1]
sys.path.insert(0, str(CHIP))

from harness import counts, models, stats, trace, traffic  # noqa: E402
from harness.peaks import UnknownDevice, peaks_for  # noqa: E402

BENCH = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
ENV = dict(os.environ, JAX_PLATFORMS="cpu")
SECONDS = "20"


def _run(*args, timeout=900):
    return subprocess.run(
        [sys.executable, str(CHIP / "run.py"), *args], cwd=CHECKOUT,
        env=ENV, capture_output=True, text=True, timeout=timeout)


# -- rehearsal of every cell -------------------------------------------------

@pytest.mark.parametrize("traced", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_the_result_line(cell, traced):
    p = _run("--workload", cell, "--seed", "3000000019", "--seconds", SECONDS,
             "--trace", str(traced), "--rehearse")
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    for key in ("correct", "attempted", "failed", "metrics", "device",
                "checks"):
        assert key in res
    assert list(res)[-1] == "checks"
    assert res["device"]["platform"] == "cpu"
    # a rehearsal never reports a number under a metric's name
    assert all(m["value"] is None for m in res["metrics"].values())
    kinds = BENCH["per_layer"] if traced else BENCH["end_to_end"]
    names = {m["name"] for m in kinds
             if "workloads" not in m or cell in m["workloads"]}
    assert set(res["metrics"]) <= names
    if cell.endswith(".saturated"):
        # the pre-warm covers every program the window calls
        window = next(json.loads(x[len("[window] "):])
                      for x in p.stderr.splitlines()
                      if x.startswith("[window] "))
        assert window["lowered_in_window"] == 0
    if traced:
        assert "breakdown" in res and "window_s" in res["device"]
    else:
        assert "setup_s" in res["metrics"]
    assert res["correct"], p.stderr[-2000:]


def test_a_run_without_a_tpu_fails_and_prints_no_result():
    p = _run("--workload", CELLS[0], "--seed", "1", "--seconds", "1",
             "--trace", "0", timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def test_an_unknown_device_kind_is_an_error():
    assert peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(UnknownDevice):
        peaks_for("TPU v99")


def _altered_token(real_step):
    """Every token the backend commits is altered where it is produced."""
    def step(self, seqs, gamma):
        out = real_step(self, seqs, gamma)
        vocab = self.target.cfg.vocab_size
        for s in seqs:
            toks = self.tokens_out.get(s.req_id)
            if toks:
                toks[-1] = (toks[-1] + 1) % vocab
        return out
    return step


def _unchanged_cache(real_step):
    """The step returns the KV pools it was given: the state it should
    have written is lost (the CPU does not donate, so the old pools live)."""
    def step(self, seqs, gamma):
        tpages, dpages = self.tkv.pages, self.dkv.pages
        out = real_step(self, seqs, gamma)
        self.tkv.pages, self.dkv.pages = tpages, dpages
        return out
    return step


@pytest.mark.parametrize("fault", [_altered_token, _unchanged_cache],
                         ids=["altered_token", "unchanged_cache"])
def test_a_broken_timed_path_is_not_correct(monkeypatch, fault):
    """The timed path broken underneath, the rest of a run as it is: the
    run must read not correct."""
    sys.path.insert(0, str(CHECKOUT / "src"))
    from repro.serving import real_backend
    from harness import bench

    monkeypatch.setattr(real_backend.RealBackend, "step",
                        fault(real_backend.RealBackend.step))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    res = bench.run_cell(CHECKOUT, CELLS[0], 3000000021, 20.0, trace=False,
                         rehearse=True, t_start=0.0)
    assert res["checks"]["tokens_compared"]["value"] > 0
    assert not res["correct"]
    g = res["checks"]["widest_logit_gap"]
    assert g["value"] > g["limit"]


# -- set-up --------------------------------------------------------------------

MIXES = sorted(p.stem for p in (CHIP / "traffic").glob("*.json"))


@pytest.mark.parametrize("name", MIXES)
def test_prefill_classes_cover_every_prompt_of_the_mixes(name):
    from harness import prewarm
    mix = traffic.load_traffic(name)
    lengths = traffic.Stream(mix, 1, 100).prompt_lengths
    reps = prewarm.prefill_classes(lengths, 16)
    key = lambda p: (prewarm.pow2(p), prewarm.pow2(-(-(p + 1) // 16)))
    assert {key(p) for p in reps} == {key(p) for p in lengths}
    # no sequence outgrows the widest table the mix pre-warms
    g = mix["engine"]["gamma_max"]
    longest = mix["prompt"]["max"] + mix["output"]["max"] + g + 1
    assert -(-longest // 16) <= max(mix["prewarm"]["widths"])


# -- trace reduction -----------------------------------------------------------

# a small recorded trace: device ops and host spans, seconds
# (device op events carry the instruction's full HLO text; the copy reads the
# kernel's output and is not the kernel)
KERNEL = ("%paged_attention.1 = bf16[64,28,128]{2,1,0} custom-call(bf16[64,"
          "28,128]{2,1,0} %fusion.2), custom_call_target=\"tpu_custom_call\"")
COPY = ("%copy.7 = bf16[64,28,128]{2,1,0} copy(bf16[64,28,128]{2,1,0} "
        "%paged_attention.1)")
OPS = [("fusion.1", 0.00, 0.10), (KERNEL, 0.05, 0.10),
       ("fusion.2", 0.30, 0.20), (KERNEL, 0.60, 0.05),
       (COPY, 0.90, 0.20)]
SPANS = [("engine.step", 0.0, 0.55), ("backend.step", 0.02, 0.5),
         ("engine.step", 0.58, 0.30), ("backend.prefill", 0.59, 0.1)]


def test_union_busy_and_idle():
    ops = trace.clip(OPS, 0.0, 1.0)
    assert ops[-1] == (COPY, 0.90, pytest.approx(0.10))
    flat = [t for iv in trace.union(ops) for t in iv]
    assert flat == pytest.approx([0.0, 0.15, 0.3, 0.5, 0.6, 0.65, 0.9, 1.0])
    assert trace.busy_seconds(ops) == pytest.approx(0.15 + 0.2 + 0.05 + 0.1)
    # the kernel's two events, not the copy of its output
    assert trace.kernel_seconds(ops, "paged_attention") == pytest.approx(
        0.15)
    assert trace.kernel_seconds([("paged_attention", 0.0, 0.5)],
                                "paged_attention") == 0.5
    assert trace.kernel_seconds(ops, "paged") == 0.0
    top = trace.top_ops(ops, 2)
    assert [t[0] for t in top] == ["fusion.2", "paged_attention.1"]
    gaps = trace.idle_gaps(ops, SPANS, 0.0, 1.0)
    # longest: 0.65-0.90, covered by the second engine.step (0.58-0.88)
    assert gaps[0][0] == "engine.step"
    assert gaps[0][1] == pytest.approx(0.25)
    # 0.15-0.30 lies inside backend.step, the inner span
    assert ["backend.step", pytest.approx(0.15)] in gaps


# -- percentiles -----------------------------------------------------------------

def test_percentile_interpolates():
    assert stats.percentile([1, 2, 3, 4], 0.5) == 2.5
    assert stats.percentile([5], 0.9) == 5
    assert stats.percentile(list(range(11)), 0.9) == pytest.approx(9.0)
    with pytest.raises(ValueError):
        stats.percentile([], 0.5)


@pytest.mark.parametrize("gap,tokens,limit,rehearse,want", [
    (0.05, 600, 0.125, False, True),
    (0.20, 600, 0.125, False, False),     # the gap over its limit
    (0.05, 299, 0.125, False, False),     # too few tokens compared
    (0.05, 600, None, False, False),      # a configuration with no limit
    (0.05, 16, 0.125, True, True),        # a rehearsal's short outputs
])
def test_verdict_needs_the_gap_within_its_limit_over_enough_tokens(
        gap, tokens, limit, rehearse, want):
    from harness import bench
    ok, checks = bench.verdict({"widest_logit_gap": gap, "tokens": tokens},
                               limit, rehearse=rehearse)
    assert ok is want
    assert checks["widest_logit_gap"] == {"value": gap, "limit": limit}
    assert checks["tokens_compared"]["value"] == tokens


def test_gamma0_step_seconds_by_width():
    from harness import bench
    from harness.driver import StepLog

    def st(t0, t1, gamma, admitted, width):
        return StepLog(t0, t1, "decode", 64, gamma, 64, admitted, 0, 1, 0,
                       True, width)
    steps = [st(0.0, 0.2, 0, 0, 128), st(0.2, 0.6, 0, 0, 128),
             st(0.6, 1.3, 3, 0, 128), st(1.3, 2.0, 0, 2, 256),
             st(2.0, 2.6, 0, 0, 256)]
    got = bench.gamma0_step_seconds(steps)
    assert list(got) == [128, 256]
    assert got[128] == {"n": 2, "mean_s": pytest.approx(0.3)}
    assert got[256] == {"n": 1, "mean_s": pytest.approx(0.6)}


def test_spread_is_the_quartile_distance_over_the_median():
    assert stats.spread([1.0, 1.0, 1.0, 1.0]) == 0.0
    assert stats.spread([9, 10, 10, 11]) == pytest.approx(0.15)


# -- operation and byte counts -------------------------------------------------

PAPER = models.load_config("paper-7b-1chip")
QWEN = models.load_config("qwen3-14b-1chip")


def test_layer_matmul_params_at_both_widths():
    # q 3584x3584, k/v 3584x512 each, o 3584x3584, SwiGLU 3 x 3584x18944
    assert counts.layer_matmul_params(PAPER["target"]) == (
        2 * 3584 * 3584 + 2 * 3584 * 512 + 3 * 3584 * 18944)
    # q 5120x5120, k/v 5120x1024 each, o 5120x5120, SwiGLU 3 x 5120x17408
    assert counts.layer_matmul_params(QWEN["target"]) == (
        2 * 5120 * 5120 + 2 * 5120 * 1024 + 3 * 5120 * 17408)
    assert counts.layer_matmul_params(QWEN["draft"]) == (
        1024 * 2048 + 2 * 1024 * 1024 + 2048 * 1024 + 3 * 1024 * 3072)


def test_prompt_and_token_flops():
    s = PAPER["target"]
    n = counts.layer_matmul_params(s)
    # a 2-token prompt: 2 x 14 x n per token, attention pairs 1 + 2 = 3
    assert counts.prompt_flops(s, 2) == 2 * 14 * n * 2 + 4 * 14 * 28 * 128 * 3
    assert counts.head_flops(s) == 2 * 3584 * 152064
    assert counts.token_flops(s, 10) == (2 * 14 * n + 4 * 14 * 28 * 128 * 10
                                         + 2 * 3584 * 152064)


def test_attention_call_counts():
    s = QWEN["target"]            # 40 query / 8 KV heads of 128
    # decode row: 1 query over 100 keys
    f, b = counts.attention_call(s, [(1, 100)])
    assert f == 4 * 40 * 128 * 100
    assert b == 2 * 100 * 8 * 128 * 2 + 2 * 1 * 40 * 128 * 2
    # verify row: 6 queries ending at 106 keys: 100+101+...+106 - ... pairs
    f, _ = counts.attention_call(s, [(6, 106)])
    assert f == 4 * 40 * 128 * sum(range(101, 107))
    pk = peaks_for("TPU v5 lite")
    f, b = counts.attention_call(s, [(1, 4096)])
    assert counts.roofline_seconds(f, b, pk) == pytest.approx(b / 819e9)


# -- traffic ------------------------------------------------------------------

def test_every_seed_offers_the_same_work_in_another_order():
    mix = traffic.load_traffic("chat.saturated")
    a = traffic.Stream(mix, 3000000001, 1000)
    b = traffic.Stream(mix, 12, 1000)
    n = mix["block"]
    ra = [a.request(i) for i in range(n)]
    rb = [b.request(i) for i in range(n)]
    assert sorted(r.prompt_len for r in ra) == sorted(r.prompt_len for r in rb)
    assert sorted(r.output_len for r in ra) == sorted(r.output_len for r in rb)
    assert [r.prompt_len for r in ra] != [r.prompt_len for r in rb]
    assert a.tokens(5, 8) == traffic.Stream(mix, 3000000001, 1000).tokens(5, 8)
    assert all(16 <= r.prompt_len <= mix["prompt"]["max"] for r in ra)



def test_the_chat_mix_and_its_clipped_cut_differ_only_in_the_clips():
    full = traffic.load_traffic("chat.saturated")
    cut = traffic.load_traffic("chat-clip1000.saturated")
    assert (full["prompt"]["max"], full["output"]["max"]) == (2048, 1024)
    assert (cut["prompt"]["max"], cut["output"]["max"]) == (1000, 1000)
    same = {k for k in full if k not in ("name", "about", "prompt", "output",
                                         "prewarm")}
    assert {k: full[k] for k in same} == {k: cut[k] for k in same}
    for law in ("prompt", "output"):
        assert ({k: v for k, v in full[law].items() if k != "max"}
                == {k: v for k, v in cut[law].items() if k != "max"})
