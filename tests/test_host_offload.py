"""Host-memory KV offload tier: HostKVStore LRU/pinning semantics, the
spill-on-evict / restore-on-match flow through BlockManager, admission
accounting for restorable blocks, the contraction bugfix (below-boundary
cached blocks survive a pool shrink), a randomized spill/restore soak with
a physical-pool byte-identity oracle, and the sim-engine e2e on the
multi-turn session workload."""
import numpy as np
import pytest

from repro import configs
from repro.serving.costmodel import RTX_4090
from repro.serving.kv_cache import (BlockManager, HostKVStore, OutOfBlocks,
                                    PhysicalKVPool, chain_hash, CHAIN_ROOT)
from repro.serving.simulator import SimConfig, build_sim_engine
from repro.serving.workload import session_requests

BS = 4  # block size for the logical tests


def _bm(nb=16, host_blocks=64, prefix_caching=True, host=True):
    hs = HostKVStore(host_blocks) if host else None
    return BlockManager(nb, BS, prefix_caching=prefix_caching, host_store=hs)


def _prompt(rng, n_blocks):
    return [int(t) for t in rng.integers(0, 1000, size=n_blocks * BS)]


def _admit(bm, seq_id, tokens):
    """Materialise a prompt the way the scheduler does: match, share,
    grow to full length, register."""
    blocks, cached = bm.match_prefix(tokens)
    if blocks:
        bm.share(seq_id, blocks, cached)
        bm.grow_to(seq_id, len(tokens))
    else:
        bm.allocate(seq_id, len(tokens))
    bm.register_prefix(seq_id, tokens, len(tokens))
    return cached


def _sim_drain(bm):
    """The simulated tier's transfer drain: spills are already indexed at
    eviction time; restores consume their record (move semantics)."""
    bm.drain_pending_spills()
    for h, _ in bm.drain_pending_restores():
        bm.host_store.take(h)


# ---------------------------------------------------------------------------
# HostKVStore unit semantics
# ---------------------------------------------------------------------------


def test_host_store_lru_eviction_and_reput():
    hs = HostKVStore(2)
    hs.put(1, 0, (1,) * BS)
    hs.put(2, 0, (2,) * BS)
    hs.put(1, 0, (1,) * BS)            # re-put refreshes LRU, no new record
    assert hs.stats["spills"] == 2
    hs.put(3, 0, (3,) * BS)            # capacity 2: LRU (hash 2) evicted
    assert set(hs.records) == {1, 3}
    assert hs.stats["host_evictions"] == 1
    assert hs.get(2) is None and hs.get(1) is not None


def test_host_store_pinned_records_survive_capacity():
    hs = HostKVStore(2)
    hs.put(1, 0, (1,) * BS)
    hs.put(2, 0, (2,) * BS)
    hs.pin(1)
    hs.put(3, 0, (3,) * BS)            # 1 is LRU but pinned: 2 goes instead
    assert set(hs.records) == {1, 3}
    hs.pin(3)
    hs.put(4, 0, (4,) * BS)            # every older record pinned: the new
    assert set(hs.records) == {1, 3}   # (unpinned) spill is the one dropped
    assert 4 not in hs.pinned


def test_host_store_take_moves_and_unpins():
    hs = HostKVStore(4)
    hs.put(7, 0, (7,) * BS)
    hs.pin(7)
    rec = hs.take(7)
    assert rec is not None and 7 not in hs.records and 7 not in hs.pinned
    assert hs.stats["restores"] == 1
    assert hs.take(7) is None          # second take: record is gone


# ---------------------------------------------------------------------------
# spill on eviction, restore on match
# ---------------------------------------------------------------------------


def test_eviction_spills_and_match_restores():
    rng = np.random.default_rng(0)
    bm = _bm(nb=8)
    hs = bm.host_store
    tokens = _prompt(rng, 3)
    _admit(bm, 0, tokens)
    bm.release(0)                       # 3 registered blocks park cached
    assert len(bm.cached) == 3

    # allocation pressure evicts the whole cached tier → host records
    bm.allocate(1, 8 * BS)
    assert len(hs.records) == 3 and len(bm.pending_spills) == 3
    assert not bm.hash_index            # device index emptied
    bm.check_invariants()
    _sim_drain(bm)
    bm.release(1)

    # the next admission's match walks into the host tier
    blocks, cached = bm.match_prefix(tokens)
    assert cached == 3 * BS and len(blocks) == 3
    assert bm.stats["restored_blocks"] == 3
    assert len(bm.pending_restores) == 3
    assert all(h in hs.pinned for h, _ in bm.pending_restores)
    # restored blocks are registered AND cached → admission counts them
    assert all(b in bm.cached for b in blocks)
    bm.check_invariants()
    _sim_drain(bm)
    assert len(hs.records) == 0         # move semantics: host copy consumed
    bm.check_invariants()

    # and they are shareable like any cached prefix
    bm.share(2, blocks, cached)
    bm.check_invariants()
    assert bm.lengths[2] == cached


def test_restore_needs_a_free_block():
    rng = np.random.default_rng(1)
    bm = _bm(nb=4)
    tokens = _prompt(rng, 2)
    _admit(bm, 0, tokens)
    bm.release(0)
    bm.allocate(1, 4 * BS)              # evict + occupy the whole pool
    _sim_drain(bm)
    assert len(bm.host_store.records) == 2
    blocks, cached = bm.match_prefix(tokens)
    assert blocks == [] and cached == 0   # no free block: restore refused
    assert not bm.pending_restores
    bm.check_invariants()


def test_register_prefix_supersedes_host_record():
    """A prompt re-prefilled on device (restore skipped) drops the host
    record at registration — the tiers stay disjoint (I6)."""
    rng = np.random.default_rng(2)
    bm = _bm(nb=4)
    hs = bm.host_store
    tokens = _prompt(rng, 2)
    _admit(bm, 0, tokens)
    bm.release(0)
    bm.allocate(1, 4 * BS)              # spill both blocks
    _sim_drain(bm)
    bm.release(1)
    assert len(hs.records) == 2
    # re-materialise WITHOUT matching first (monolithic re-prefill)
    bm.allocate(2, len(tokens))
    bm.register_prefix(2, tokens, len(tokens))
    assert len(hs.records) == 0         # device copy superseded the host's
    bm.check_invariants()


def test_evicting_restore_target_cancels_restore():
    """When allocation pressure evicts a block that is itself a pending
    restore TARGET, the restore is cancelled and the host record (still the
    content's only owner) survives, unpinned."""
    rng = np.random.default_rng(3)
    bm = _bm(nb=4)
    hs = bm.host_store
    tokens = _prompt(rng, 2)
    _admit(bm, 0, tokens)
    bm.release(0)
    bm.allocate(1, 4 * BS)
    _sim_drain(bm)
    bm.release(1)
    blocks, cached = bm.match_prefix(tokens)
    assert cached == 2 * BS and len(bm.pending_restores) == 2
    # pressure again: the restore targets are LRU-cached, so they evict
    bm.allocate(2, 4 * BS)
    assert not bm.pending_restores       # both restores cancelled
    assert len(hs.records) == 2          # records kept — sole content owner
    assert not hs.pinned                 # and unpinned
    bm.check_invariants()
    # no spurious spills of never-materialised targets
    spilled = {h for _, h in bm.pending_spills}
    for h, _ in list(hs.records.items()):
        assert h not in spilled or hs.records[h] is not None


# ---------------------------------------------------------------------------
# contraction: the below-boundary preservation bugfix + spill-on-contract
# ---------------------------------------------------------------------------


def test_contraction_preserves_below_boundary_cached():
    """Regression (pre-fix: plan_contraction evicted EVERY cached block,
    cold-restarting the prefix cache on each contraction).  Warm cached
    blocks below the boundary must keep their registrations, and the next
    templated admission must still hit."""
    rng = np.random.default_rng(4)
    bm = BlockManager(8, BS, prefix_caching=True)
    tokens = _prompt(rng, 3)
    _admit(bm, 0, tokens)               # occupies low ids
    bm.release(0)                       # → cached, below boundary
    cached_hashes = set(bm.hash_index)
    assert len(cached_hashes) == 3

    bm.expand(4)                        # boundary stays 8, total 12
    plan = bm.plan_contraction()
    assert plan is not None and len(plan) == 0
    bm.commit_contraction(plan)
    bm.check_invariants()

    # the fix: warm below-boundary registrations survived the shrink
    assert set(bm.hash_index) == cached_hashes
    blocks, cached = bm.match_prefix(tokens)
    assert cached == 3 * BS
    bm.check_invariants()


def test_contraction_evicts_above_boundary_to_host():
    """Cached blocks living in the doomed region spill to the host tier at
    plan time and restore after the shrink."""
    rng = np.random.default_rng(5)
    bm = _bm(nb=4)
    hs = bm.host_store
    bm.allocate(0, 4 * BS)              # pin the base region
    bm.expand(4)                        # ids 4..7
    tokens = _prompt(rng, 2)
    _admit(bm, 1, tokens)               # lands in the expanded region
    high = list(bm.tables[1])
    assert all(b >= 4 for b in high)
    bm.release(1)                       # → cached, above boundary
    bm.release(0)

    plan = bm.plan_contraction()
    assert plan is not None
    bm.commit_contraction(plan)
    bm.check_invariants()
    assert len(hs.records) == 2          # spilled, not discarded
    _sim_drain(bm)

    blocks, cached = bm.match_prefix(tokens)
    assert cached == 2 * BS              # restored into the shrunk pool
    bm.check_invariants()


def test_contraction_evicts_minimum_low_cached_for_targets():
    """When the preserved region has too few free slots for the migration,
    only the minimum number of low cached blocks are evicted (LRU-first) —
    the rest keep their registrations."""
    rng = np.random.default_rng(6)
    bm = _bm(nb=6)
    t_a, t_b = _prompt(rng, 2), _prompt(rng, 2)
    _admit(bm, 0, t_a)
    _admit(bm, 1, t_b)
    bm.release(0)
    bm.release(1)                       # 4 low cached blocks, 2 free low
    bm.expand(2)
    bm.allocate(2, 2 * BS)              # pins ids 4,5... wherever free
    high = [b for b in bm.tables[2] if b >= bm.boundary]
    if not high:                        # allocation came from low free ids:
        pytest.skip("allocator gave low ids; nothing to migrate")
    plan = bm.plan_contraction()
    assert plan is not None
    bm.commit_contraction(plan)
    bm.check_invariants()
    # at most len(high) low cached evictions; the other registrations live
    assert len(bm.hash_index) >= 4 - len(high)


# ---------------------------------------------------------------------------
# randomized spill/restore soak with a physical byte-identity oracle
# ---------------------------------------------------------------------------

L, KH, HD = 2, 1, 2   # tiny physical pool geometry


def _block_payload(tokens):
    """Deterministic per-block K/V content derived from the token ids —
    the oracle for byte-identity through spill→restore round trips."""
    t = np.asarray(tokens, np.float32)
    k = np.broadcast_to(t[None, :, None, None], (L, len(tokens), KH, HD))
    return k, k * 2.0 + 1.0


def _flush(bm, pool):
    """The physical tier's transfer drain (mirrors
    RealBackend.apply_host_transfers): gather spills into their records,
    then scatter pinned restore payloads into their target blocks."""
    hs = bm.host_store
    spills = [(b, h) for b, h in bm.drain_pending_spills()
              if h in hs.records]
    if spills:
        kpay, vpay = pool.spill_blocks([b for b, _ in spills])
        for i, (_, h) in enumerate(spills):
            hs.records[h].data = {"k": np.asarray(kpay[:, i]),
                                  "v": np.asarray(vpay[:, i])}
            hs.seal(h)   # re-stamp the checksum over the filled pages
    restores = bm.drain_pending_restores()
    if restores:
        recs = [hs.take(h) for h, _ in restores]
        assert all(r is not None and r.data for r in recs), \
            "pinned host record lost before its restore drained"
        pool.restore_blocks([b for _, b in restores],
                            np.stack([r.data["k"] for r in recs], axis=1),
                            np.stack([r.data["v"] for r in recs], axis=1))


def _write_range(pool, table, tokens, start):
    """Materialise prompt positions [start, len(tokens)) into the pool."""
    if start >= len(tokens):
        return
    k, v = _block_payload(tokens[start:])
    pool.write_tokens(k, v, table, start)


def _assert_registered_bytes(bm, pool):
    """Every registered device block (restores drained) holds exactly the
    content its token chain dictates."""
    assert not bm.pending_restores
    for b, (_, toks) in bm.block_chain.items():
        ek, ev = _block_payload(toks)
        np.testing.assert_array_equal(np.asarray(pool.k[:, b]), ek)
        np.testing.assert_array_equal(np.asarray(pool.v[:, b]), ev)


def _assert_no_leaks(bm):
    owned = set(bm.free) | set(bm.cached) | set(bm.refcount) | bm.reserved
    assert owned == set(range(bm.total_blocks)), \
        f"leaked blocks: {set(range(bm.total_blocks)) - owned}"


def test_randomized_spill_restore_soak():
    rng = np.random.default_rng(42)
    nb = 24
    bm = _bm(nb=nb, host_blocks=96)
    pool = PhysicalKVPool(L, nb, BS, KH, HD, dtype=np.float32)
    prompts = []          # grown session-style so prefixes repeat
    live = {}             # seq_id -> prompt
    next_seq = 0

    for step in range(140):
        op = rng.choice(["admit", "release", "flush", "contract_cycle"],
                        p=[0.45, 0.25, 0.2, 0.1])
        if op == "admit":
            if prompts and rng.uniform() < 0.6:
                base = prompts[int(rng.integers(len(prompts)))]
                tokens = base + _prompt(rng, int(rng.integers(1, 3)))
            else:
                tokens = _prompt(rng, int(rng.integers(1, 4)))
            need = bm.blocks_needed(len(tokens))
            if need > bm.num_allocatable:
                continue
            sid = next_seq
            next_seq += 1
            cached = _admit(bm, sid, tokens)
            # drain BEFORE writing, exactly like the engine step: evictions
            # queued by this admission spill the pre-overwrite content, and
            # queued restores land before the new suffix is written
            _flush(bm, pool)
            _write_range(pool, bm.tables[sid], tokens, cached)
            live[sid] = tokens
            if len(prompts) < 40:
                prompts.append(tokens)
        elif op == "release" and live:
            sid = list(live)[int(rng.integers(len(live)))]
            bm.release(sid)
            del live[sid]
        elif op == "flush":
            _flush(bm, pool)
        elif op == "contract_cycle":
            _flush(bm, pool)
            bm.expand(8)
            pool.grow(8)
            # park some load in the expanded region, then shrink back
            if bm.num_allocatable >= 2:
                sid = next_seq
                next_seq += 1
                tokens = _prompt(rng, 2)
                cached = _admit(bm, sid, tokens)
                _flush(bm, pool)
                _write_range(pool, bm.tables[sid], tokens, cached)
                live[sid] = tokens
            plan = bm.plan_contraction()
            if plan is not None:
                _flush(bm, pool)         # capture plan-time spills FIRST
                pool.migrate(plan)
                bm.commit_contraction(plan)
                pool.shrink(bm.total_blocks)
            # plan can legitimately fail under load (not enough low free
            # slots): the pool simply stays expanded until a later cycle
        bm.check_invariants()
        _assert_no_leaks(bm)
        if step % 10 == 0:
            _flush(bm, pool)
            _assert_registered_bytes(bm, pool)

    _flush(bm, pool)
    bm.check_invariants()
    _assert_no_leaks(bm)
    _assert_registered_bytes(bm, pool)
    # the soak actually exercised the tier both ways
    hs = bm.host_store
    assert hs.stats["spills"] > 0 and hs.stats["restores"] > 0


# ---------------------------------------------------------------------------
# sim-engine e2e: the multi-turn session workload
# ---------------------------------------------------------------------------


def _sessions_run(kv_offload):
    cfg = SimConfig(target=configs.get_config("paper-7b"),
                    draft=configs.get_draft_config("paper-7b"),
                    hw=RTX_4090, chunk_tokens=384, prefix_caching=True,
                    enable_offload=False, num_blocks=256,
                    kv_offload=kv_offload, seed=0)
    eng = build_sim_engine(cfg, "nightjar")
    reqs = session_requests(6, turns=4, rate_qps=0.5, seed=1)
    m = eng.run(reqs, record_timeline=False)
    eng.scheduler.bm.check_invariants()
    return m


def test_sessions_engine_offload_improves_cross_turn_hits():
    m_on = _sessions_run(True)
    m_off = _sessions_run(False)

    def hit_rate(m):
        warm = [r for r in m.requests if r.turn > 0]
        return sum(1 for r in warm if r.cached_tokens > 0) / len(warm)

    assert len(m_on.requests) == len(m_off.requests) > 0
    assert m_on.host["restores"] > 0
    assert m_on.host["restore_s"] > 0          # priced at host_link_bw
    assert hit_rate(m_on) > hit_rate(m_off)
    # restores move bytes, never change computation: identical streams
    assert sorted((r.req_id, r.tokens) for r in m_on.requests) == \
        sorted((r.req_id, r.tokens) for r in m_off.requests)
    # metrics surface the tier
    s = m_on.summary()
    assert s["host_spills"] > 0 and s["host_restores"] > 0
    assert "host" not in m_off.summary().get("host", {})  # off → no keys
    assert "host_spills" not in m_off.summary()


def test_sessions_engine_restored_blocks_counted_cached():
    """Admission accounting: restored prefix blocks show up as
    cached_tokens on the requests that hit them (the scheduler's
    match→share path treats them like any cached block)."""
    m = _sessions_run(True)
    warm_hits = [r for r in m.requests if r.turn > 0 and r.cached_tokens > 0]
    assert warm_hits, "no warm request admitted with cached prefix"
    assert m.prefix.get("restored_blocks", 0) > 0
