"""Paged-KV runtime for the real backend.

Tier-1 (fast, CPU): model-level paged-vs-dense logit equivalence (decode,
speculative-verify extension, ragged chunked prefill), trash-block write
isolation, BlockManager capacity reservation, pool sizing from the roofline
HBM budget, and the adaptive chunk-budget knee.

Slow tier (real execution e2e): dense-vs-paged engines emit identical
greedy token streams, chunked real prefill equals monolithic prefill, and
preempt-and-recompute under severe memory pressure stays lossless.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.bandits import make_policy
from repro.kernels import ops
from repro.models import registry
from repro.serving.costmodel import (RTX_4090, RooflineCostModel,
                                     kv_bytes_per_token)
from repro.serving.engine import ServingEngine
from repro.serving.kv_cache import BlockManager, OutOfBlocks
from repro.serving.paged_runtime import PagedKVRuntime, num_blocks_for
from repro.serving.real_backend import (DenseSlotBackend, RealBackend,
                                        make_real_backend)
from repro.serving.scheduler import ContinuousBatchingScheduler
from repro.serving.workload import tiny_requests


def _api(arch, draft=False):
    get = configs.get_draft_config if draft else configs.get_config
    return registry.get_model(
        configs.reduced(get(arch)).replace(dtype="float32"))


# ---------------------------------------------------------------------------
# tier-1: model-level equivalence with the dense cache path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["deepseek-7b", "granite-moe-1b-a400m"])
def test_paged_decode_matches_dense(arch):
    """Paged prefill (start=0), T=1 decode and T=3 verify extensions all
    produce the same logits as the dense slot-cache path."""
    api = _api(arch)
    cfg = api.cfg
    params = api.init(jax.random.PRNGKey(0))
    B, S = 2, 12
    tok = jax.random.randint(jax.random.PRNGKey(1), (B, S + 4), 0,
                             cfg.vocab_size)

    lg_p, cache = api.prefill(params, {"tokens": tok[:, :S]}, S + 8)
    pages = api.init_paged_cache(16, 4)
    tables = jnp.asarray([[0, 1, 2, 3, 4], [5, 6, 7, 8, 9]], jnp.int32)
    lg_paged, pages = api.decode_step_paged(params, pages, tok[:, :S],
                                            tables, jnp.zeros((B,), jnp.int32))
    np.testing.assert_allclose(np.asarray(lg_paged[:, -1]),
                               np.asarray(lg_p[:, 0]), atol=1e-4)

    lg1, cache = api.decode_step(params, cache, tok[:, S:S + 1])
    lg1p, pages = api.decode_step_paged(params, pages, tok[:, S:S + 1],
                                        tables, jnp.full((B,), S, jnp.int32))
    np.testing.assert_allclose(np.asarray(lg1p), np.asarray(lg1), atol=1e-4)

    lg3, cache = api.decode_step(params, cache, tok[:, S + 1:S + 4])
    lg3p, pages = api.decode_step_paged(params, pages, tok[:, S + 1:S + 4],
                                        tables,
                                        jnp.full((B,), S + 1, jnp.int32))
    np.testing.assert_allclose(np.asarray(lg3p), np.asarray(lg3), atol=1e-4)


def test_paged_chunked_prefill_matches_monolithic():
    """Ragged chunked appends (per-row valid counts) reach the same
    last-position logits as one monolithic paged prefill."""
    api = _api("deepseek-7b")
    params = api.init(jax.random.PRNGKey(0))
    B, S = 2, 12
    tok = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                             api.cfg.vocab_size)
    tables = jnp.asarray([[0, 1, 2, 3], [5, 6, 7, 8]], jnp.int32)

    mono_pages = api.init_paged_cache(16, 4)
    lg_mono, _ = api.decode_step_paged(params, mono_pages, tok, tables,
                                       jnp.zeros((B,), jnp.int32))

    pages = api.init_paged_cache(16, 4)
    # seq0 chunks 5+7, seq1 chunks 7+5 (padded rows exercise the trash path)
    c1 = jnp.stack([jnp.pad(tok[0, :5], (0, 2)), tok[1, :7]])
    _, pages = api.decode_step_paged(params, pages, c1, tables,
                                     jnp.zeros((B,), jnp.int32),
                                     jnp.asarray([5, 7]))
    c2 = jnp.stack([tok[0, 5:12], jnp.pad(tok[1, 7:12], (0, 2))])
    lg, pages = api.decode_step_paged(params, pages, c2, tables,
                                      jnp.asarray([5, 7]),
                                      jnp.asarray([7, 5]))
    last = jnp.stack([lg[0, 6], lg[1, 4]])
    np.testing.assert_allclose(np.asarray(last), np.asarray(lg_mono[:, -1]),
                               atol=1e-4)


def test_paged_shared_prefix_matches_dense():
    """Two sequences SHARING physical prefix blocks (written once) produce
    the same logits as dense full-prompt prefill — the model-level
    correctness of prefix-cache admission."""
    api = _api("deepseek-7b")
    cfg = api.cfg
    params = api.init(jax.random.PRNGKey(0))
    S, P = 8, 12                               # 8 shared + 4 private tokens
    key = jax.random.PRNGKey(1)
    prefix = jax.random.randint(key, (S,), 0, cfg.vocab_size)
    sfx = jax.random.randint(jax.random.PRNGKey(2), (2, P - S), 0,
                             cfg.vocab_size)
    prompts = jnp.stack([jnp.concatenate([prefix, sfx[0]]),
                         jnp.concatenate([prefix, sfx[1]])])

    # dense baseline: both prompts prefilled independently
    lg_dense, _ = api.prefill(params, {"tokens": prompts}, P + 4)

    pages = api.init_paged_cache(16, 4)
    # seq0 writes the prefix (blocks 0,1) + its private block 2
    t0 = jnp.asarray([[0, 1, 2]], jnp.int32)
    lg0, pages = api.decode_step_paged(params, pages, prompts[:1], t0,
                                       jnp.zeros((1,), jnp.int32))
    # seq1 SHARES blocks 0,1 and only extends from the match boundary
    t1 = jnp.asarray([[0, 1, 3]], jnp.int32)
    lg1, pages = api.decode_step_paged(params, pages, sfx[1:2], t1,
                                       jnp.full((1,), S, jnp.int32))
    np.testing.assert_allclose(np.asarray(lg0[0, -1]),
                               np.asarray(lg_dense[0, 0]), atol=1e-4)
    np.testing.assert_allclose(np.asarray(lg1[0, -1]),
                               np.asarray(lg_dense[1, 0]), atol=1e-4)


def test_cow_fork_copy_preserves_logits():
    """Forking a shared block (apply_copies through the block-migration
    kernel path) leaves the forked sequence's logits identical to an
    unshared run — CoW is invisible to the model."""
    api = _api("deepseek-7b")
    cfg = api.cfg
    params = api.init(jax.random.PRNGKey(0))
    P = 8                                      # exactly 2 full blocks
    tok = jax.random.randint(jax.random.PRNGKey(1), (1, P), 0,
                             cfg.vocab_size)

    from repro.serving.kv_cache import BlockManager
    from repro.serving.paged_runtime import PagedKVRuntime
    bm = BlockManager(8, 4, prefix_caching=True)
    rt = PagedKVRuntime(api, bm)
    bm.allocate(1, P)
    tbl1 = jnp.asarray([bm.tables[1]], jnp.int32)
    _, rt.pages = api.decode_step_paged(params, rt.pages, tok, tbl1,
                                        jnp.zeros((1,), jnp.int32))
    bm.register_prefix(1, [int(t) for t in tok[0]], P)
    # seq 2: fully cached prompt -> share both blocks, fork the tail for
    # the capped last-token recompute
    blocks, matched = bm.match_prefix([int(t) for t in tok[0]])
    assert matched == P
    bm.share(2, blocks, P - 1)
    (src, dst), = bm.fork_for_write(2, P - 1, P)
    with ops.interpret_kernels():
        rt.apply_copies(*zip(*bm.drain_pending_copies()))
    tbl2 = jnp.asarray([bm.tables[2]], jnp.int32)
    # recompute the last prompt token into the PRIVATE copy
    lg2, rt.pages = api.decode_step_paged(params, rt.pages, tok[:, -1:],
                                          tbl2,
                                          jnp.full((1,), P - 1, jnp.int32))
    # baseline: the same last-token extension on the original table
    lg1, _ = api.decode_step_paged(params, rt.pages, tok[:, -1:], tbl1,
                                   jnp.full((1,), P - 1, jnp.int32))
    np.testing.assert_allclose(np.asarray(lg2), np.asarray(lg1), atol=1e-5)
    bm.check_invariants()


def test_invalid_slots_write_only_the_trash_block():
    """Padded/invalid token slots must never touch a live block: with
    valid=0 every non-trash page is bit-identical before and after."""
    api = _api("deepseek-7b")
    params = api.init(jax.random.PRNGKey(0))
    pages = api.init_paged_cache(8, 4)
    before = jax.tree.map(lambda x: np.asarray(x), pages)
    tok = jnp.zeros((1, 4), jnp.int32)
    tables = jnp.asarray([[0, 1]], jnp.int32)
    _, after = api.decode_step_paged(params, pages, tok, tables,
                                     jnp.zeros((1,), jnp.int32),
                                     jnp.zeros((1,), jnp.int32))
    for key in ("k_pages", "v_pages"):
        got = np.asarray(after[key])
        np.testing.assert_array_equal(got[:, :8], before[key][:, :8])
        assert np.any(got[:, 8] != before[key][:, 8])  # trash absorbed it


# ---------------------------------------------------------------------------
# tier-1: BlockManager capacity reservation + pool sizing
# ---------------------------------------------------------------------------


def test_ensure_capacity_reserves_without_length_change():
    bm = BlockManager(10, 4)
    bm.allocate(1, 6)                      # 2 blocks, length 6
    added = bm.ensure_capacity(1, 13)      # needs 4 blocks total
    assert len(added) == 2 and len(bm.tables[1]) == 4
    assert bm.lengths[1] == 6              # logical length untouched
    bm.check_invariants()
    # the later commit allocates nothing for already-covered positions
    free = bm.num_free
    bm.append_tokens(1, 7)
    assert bm.num_free == free and bm.lengths[1] == 13
    assert bm.ensure_capacity(1, 10) == [] # no-op when covered


def test_ensure_capacity_out_of_blocks():
    bm = BlockManager(3, 4)
    bm.allocate(1, 8)
    bm.allocate(2, 4)
    with pytest.raises(OutOfBlocks):
        bm.ensure_capacity(1, 16)
    bm.check_invariants()


def test_num_blocks_for_sizes_pool_from_roofline_budget():
    cm = RooflineCostModel(RTX_4090)
    t = configs.get_config("paper-7b")
    d = configs.get_draft_config("paper-7b")
    nb = num_blocks_for(cm, t, d, 16)
    # the bytes beside both weights, over one block of BOTH pools plus one
    # target page array's block (the resize copy of the elastic cycle)
    per_block = 16 * (kv_bytes_per_token(t) + kv_bytes_per_token(d)
                      + kv_bytes_per_token(t) // 2)
    avail = (RTX_4090.hbm_bytes * 0.9 - cm.weight_bytes(t)
             - cm.weight_bytes(d))
    assert nb == int(avail // per_block)
    assert nb < cm.kv_capacity_tokens(t, d) // 16   # target pool alone
    assert num_blocks_for(cm, t, d, 16, max_blocks=512) == 512  # clamped
    tiny = configs.reduced(t)
    assert num_blocks_for(cm, tiny, configs.reduced(d), 8,
                          max_blocks=4096) == 4096


def test_runtime_batch_tables_pad_with_trash():
    bm = BlockManager(8, 4)
    rt = PagedKVRuntime(_api("deepseek-7b"), bm)
    from repro.serving.request import Request, Sequence
    s = Sequence(request=Request(1, 0.0, 6, 4))
    bm.allocate(1, 6)
    rt.ctx[1] = 6
    tables, lengths = rt.batch_tables([s], 4)
    assert tables.shape == (4, 2) and lengths.tolist() == [6, 0, 0, 0]
    assert set(tables[0]) <= set(bm.tables[1])
    assert (tables[1:] == rt.trash).all()


# ---------------------------------------------------------------------------
# tier-1: elastic PHYSICAL pool on the real tier (grow / migrate / shrink)
# ---------------------------------------------------------------------------


def test_runtime_grow_shrink_tracks_block_manager():
    """PagedKVRuntime.grow/shrink keep the physical pages, trash id and
    BlockManager pool size in lockstep — the §6.3/6.4 wiring that lets the
    elastic memory manager run on real execution."""
    bm = BlockManager(8, 4)
    api = _api("deepseek-7b")
    rt = PagedKVRuntime(api, bm)
    L = api.cfg.num_layers
    assert rt.pages["k_pages"].shape[1] == 9        # 8 + trash
    # stamp recognisable content into block 3
    rt.pages["k_pages"] = rt.pages["k_pages"].at[:, 3].set(7.0)

    bm.expand(4)
    rt.grow(4)
    assert rt.num_blocks == bm.total_blocks == 12
    assert rt.trash == 12
    assert rt.pages["k_pages"].shape[1] == 13
    # pre-existing content survives the grow
    assert float(rt.pages["k_pages"][0, 3, 0, 0]) == 7.0

    # a sequence landing entirely in the expanded region (the free list
    # pops the freshly attached high ids first) round-trips batch_tables
    from repro.serving.request import Request, Sequence
    bm.allocate(2, 12)
    high = [b for b in bm.tables[2] if b >= bm.boundary]
    assert len(high) == 3                           # 11, 10, 9
    s = Sequence(request=Request(2, 0.0, 12, 4))
    rt.ctx[2] = 12
    tables, lengths = rt.batch_tables([s], 1)
    assert lengths.tolist() == [12]
    assert set(tables[0][:3].tolist()) == set(bm.tables[2])

    # §6.4: migrate the high blocks into the preserved region, then shrink
    rt.pages["k_pages"] = rt.pages["k_pages"].at[:, high[0]].set(3.0)
    plan = bm.plan_contraction()
    assert plan is not None and set(plan.src) == set(high)
    rt.apply_plan(plan)
    bm.commit_contraction(plan)
    rt.shrink(bm.base_blocks)
    assert rt.num_blocks == bm.total_blocks == 8 and rt.trash == 8
    assert all(b < bm.boundary for b in bm.tables[2])
    moved = bm.tables[2][0]                         # high[0]'s new home
    assert float(rt.pages["k_pages"][0, moved, 0, 0]) == 3.0
    bm.check_invariants()


def test_memmgr_drives_physical_pool_hooks():
    """ElasticMemoryManager grow_fn/shrink_fn/migrate_fn fire in lockstep
    with the logical expand/contract cycle (recorded via stub hooks)."""
    from repro.serving.memory_manager import ElasticMemoryManager
    bm = BlockManager(8, 4)
    events = []
    mm = ElasticMemoryManager(
        bm, draft_blocks=4, t_persist=1, tau_low_frac=0.5,
        offload_fn=lambda: events.append("offload"),
        reload_fn=lambda: events.append("reload"),
        migrate_fn=lambda plan: events.append(("migrate", len(plan))) or 0.0,
        grow_fn=lambda extra: events.append(("grow", extra)),
        shrink_fn=lambda nb: events.append(("shrink", nb)))
    bm.allocate(1, 8 * 4)                 # pool full -> low-memory streak
    mm.step(0.0, spec_disabled=True, waiting=4)
    assert ("grow", 4) in events and "offload" in events
    assert bm.total_blocks == 12
    bm.release(1)                          # drained queue -> contraction
    mm.step(1.0, spec_disabled=True, waiting=0)
    assert ("shrink", 8) in events and "reload" in events
    assert bm.total_blocks == 8
    bm.check_invariants()


def test_memmgr_events_carry_measured_hook_seconds():
    """Each event records the wall seconds of the hooks it ran; the
    modelled latency and the transfer horizon stay as configured."""
    import time
    from repro.serving.memory_manager import ElasticMemoryManager

    def nap(*_):
        time.sleep(0.005)

    bm = BlockManager(8, 4)
    mm = ElasticMemoryManager(
        bm, draft_blocks=4, t_persist=1, tau_low_frac=0.5,
        offload_latency=0.25, reload_latency=0.5, offload_fn=nap,
        reload_fn=nap, migrate_fn=lambda plan: nap() or 0.0, grow_fn=nap,
        shrink_fn=nap)
    bm.allocate(1, 8 * 4)
    mm.step(0.0, spec_disabled=True, waiting=4)
    assert not mm.can_speculate(0.2) and mm._busy_until == 0.25
    bm.release(1)
    mm.step(1.0, spec_disabled=True, waiting=0)
    ev = {e.kind: e for e in mm.events}
    assert [e.kind for e in mm.events] == ["offload", "expand", "contract",
                                           "reload"]
    assert set(ev["offload"].wall_s) == {"offload"}
    assert set(ev["expand"].wall_s) == {"grow"}
    assert set(ev["contract"].wall_s) <= {"migrate", "shrink"}
    assert "shrink" in ev["contract"].wall_s
    assert set(ev["reload"].wall_s) == {"reload"}
    assert all(s >= 0.005 for e in mm.events for s in e.wall_s.values())
    assert (ev["offload"].latency, ev["reload"].latency) == (0.25, 0.5)
    assert mm._busy_until == 1.5


# ---------------------------------------------------------------------------
# tier-1: adaptive chunk budget (roofline knee)
# ---------------------------------------------------------------------------


def test_knee_chunk_tokens_is_roofline_crossover():
    cm = RooflineCostModel(RTX_4090)
    cfg = configs.get_config("paper-7b")
    knee = cm.knee_chunk_tokens(cfg)
    assert 16 <= knee <= 8192
    t_c, t_m = cm._hybrid_terms(cfg, knee, 0, 1024)
    assert t_c <= t_m                       # memory-bound at the knee...
    t_c, t_m = cm._hybrid_terms(cfg, knee + 1, 0, 1024)
    assert t_c > t_m                        # ...compute-bound just past it


def test_resolve_chunk_tokens():
    cm = RooflineCostModel(RTX_4090)
    cfg = configs.get_config("paper-7b")
    assert cm.resolve_chunk_tokens("auto", cfg) == cm.knee_chunk_tokens(cfg)
    assert cm.resolve_chunk_tokens("128", cfg) == 128
    assert cm.resolve_chunk_tokens(0, cfg) == 0
    assert cm.resolve_chunk_tokens("auto", None) == 256  # no model: fallback


def test_make_real_backend_selects_by_family():
    t, d = _api("mamba2-780m"), _api("mamba2-780m", draft=True)
    assert isinstance(make_real_backend(t, d, max_batch=2, max_seq=32),
                      DenseSlotBackend)
    with pytest.raises(NotImplementedError):
        RealBackend(t, d, max_batch=2, max_seq=32)


@pytest.mark.parametrize("arch", ["mamba2-780m", "zamba2-1.2b"])
def test_build_real_engine_serves_dense_slot_families(arch):
    """The real tier's builder hands a pair without a paged-KV path to
    DenseSlotBackend (no elastic memory manager) and serves it."""
    from repro.serving.real_backend import build_real_engine
    from repro.serving.workload import tiny_requests
    engine = build_real_engine(arch, reduced=True, max_batch=2, gamma_max=2)
    assert isinstance(engine.backend, DenseSlotBackend)
    assert engine.memmgr is None
    reqs = tiny_requests(3, rate_qps=100.0, prompt_len=8, output_len=6,
                         vocab=engine.backend.target.cfg.vocab_size, seed=0)
    m = engine.run(reqs, max_steps=500)
    assert {r.req_id for r in m.requests} == {r.req_id for r in reqs}
    for r in reqs:
        assert len(engine.backend.output_tokens(r.req_id)) >= r.output_len


# ---------------------------------------------------------------------------
# slow tier: engine-level equivalence on real execution
# ---------------------------------------------------------------------------


def _run_engine(backend_kind, *, chunk=None, policy="nightjar", blocks=256,
                block_size=8, n=4, prompt=10, out=8, prefix_caching=False,
                template=0, memmgr=False):
    target, draft = _api("deepseek-7b"), _api("deepseek-7b", draft=True)
    bm = BlockManager(blocks, block_size, prefix_caching=prefix_caching)
    if backend_kind == "dense":
        be = DenseSlotBackend(target, draft, max_batch=4, max_seq=96, seed=0)
    else:
        be = RealBackend(target, draft, max_batch=4, max_seq=96, seed=0,
                         block_manager=bm)
    sched = ContinuousBatchingScheduler(bm, max_batch=4, chunk_tokens=chunk,
                                        watermark_frac=0.0)
    mm = None
    if memmgr:
        from repro.serving.memory_manager import ElasticMemoryManager
        mm = ElasticMemoryManager(
            bm, draft_blocks=4, t_persist=1, tau_low_frac=0.4,
            offload_fn=be.offload_draft, reload_fn=be.reload_draft,
            migrate_fn=be.migrate_pools, grow_fn=be.grow_pools,
            shrink_fn=be.shrink_pools)
    eng = ServingEngine(be, sched, make_policy(policy, 3, seed=0), mm,
                        gamma_max=3)
    reqs = tiny_requests(n, rate_qps=1e6, prompt_len=prompt, output_len=out,
                         vocab=target.cfg.vocab_size, seed=5,
                         template_len=template)
    m = eng.run(reqs, max_steps=3000, record_timeline=True)
    return {r.req_id: be.output_tokens(r.req_id)[:out + 1] for r in reqs}, m


@pytest.mark.slow
@pytest.mark.real_backend
def test_paged_engine_matches_dense_engine():
    """Greedy token streams identical between the dense slot backend and
    the paged runtime, across AR and adaptive-speculation policies."""
    dense, _ = _run_engine("dense")
    for pol in ("ar", "nightjar"):
        paged, _ = _run_engine("paged", policy=pol)
        assert paged == dense, pol


@pytest.mark.slow
@pytest.mark.real_backend
def test_chunked_real_execution_matches_monolithic():
    """RealBackend.hybrid_step accepts prefill chunks and the chunked token
    streams equal monolithic prefill exactly (the acceptance criterion)."""
    mono, m_mono = _run_engine("paged", prompt=24, out=8)
    for chunk in (4, 7, 16):
        chunked, m = _run_engine("paged", chunk=chunk, prompt=24, out=8)
        assert chunked == mono, chunk
    # chunked mode genuinely exercised mixed steps
    assert any(r["prefill_tokens"] > 0 for r in m.timeline)


@pytest.mark.slow
@pytest.mark.real_backend
def test_paged_preempt_recompute_under_pressure_lossless():
    """A pool far too small for the workload forces preempt-and-recompute;
    the final streams still match an unconstrained run exactly."""
    squeezed, m = _run_engine("paged", chunk=6, blocks=10, block_size=4,
                              out=16)
    roomy, _ = _run_engine("paged", out=16)
    assert squeezed == roomy
    assert len(m.requests) == 4


@pytest.mark.slow
@pytest.mark.real_backend
def test_prefix_caching_real_token_equivalence():
    """Greedy token streams are byte-identical with prefix caching on vs
    off on real execution — shared templated prompts AND fully-identical
    prompts (the capped last-token recompute + CoW fork path)."""
    # 8-token shared template, 16-token prompts: half of every prompt is
    # admitted from the cache after the first request
    base, _ = _run_engine("paged", chunk=8, prompt=16, template=8)
    cached, m = _run_engine("paged", chunk=8, prompt=16, template=8,
                            prefix_caching=True)
    assert cached == base
    assert m.prefix["hits"] > 0 and m.prefix["saved_tokens"] > 0

    # fully identical prompts: every later request shares ALL blocks and
    # forks the tail block to recompute its last prompt token
    base2, _ = _run_engine("paged", chunk=8, prompt=16, template=16)
    cached2, m2 = _run_engine("paged", chunk=8, prompt=16, template=16,
                              prefix_caching=True)
    assert cached2 == base2
    assert m2.prefix["forks"] > 0          # CoW genuinely exercised


@pytest.mark.slow
@pytest.mark.real_backend
def test_elastic_physical_pool_real_execution_lossless():
    """The elastic memory manager running ON the real backend (offload ->
    bm.expand + PagedKVRuntime.grow, contract -> migrate + shrink) keeps
    greedy token streams identical to an unmanaged run."""
    managed, m = _run_engine("paged", blocks=24, block_size=4, out=12,
                             memmgr=True)
    plain, _ = _run_engine("paged", blocks=24, block_size=4, out=12)
    assert managed == plain
    # pressure on a 24-block pool with 4 sequences genuinely triggers the
    # offload/expand path at least once
    assert m.offload_events >= 1
