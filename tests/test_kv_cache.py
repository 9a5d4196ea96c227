"""Block manager + elastic pool invariants (§6.3/6.4), hypothesis-free tier.

The randomised property versions of these tests live in
tests/test_kv_cache_properties.py (skipped when hypothesis is missing);
here the same invariants are exercised with seeded, parametrized
plain-pytest equivalents so tier-1 coverage never depends on optional
dependencies.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.serving.kv_cache import (BlockManager, OutOfBlocks,
                                    PhysicalKVPool)


def test_allocate_release_roundtrip():
    bm = BlockManager(32, block_size=4)
    bm.allocate(1, 10)  # 3 blocks
    bm.allocate(2, 4)   # 1 block
    bm.check_invariants()
    assert bm.num_free == 28
    bm.release(1)
    assert bm.num_free == 31
    bm.check_invariants()


@pytest.mark.parametrize("tokens,blocks", [(1, 1), (4, 1), (5, 2),
                                           (16, 4), (17, 5)])
def test_alloc_free_roundtrip_parametrized(tokens, blocks):
    """Round-trip at block boundaries: allocation size and full recovery."""
    bm = BlockManager(16, block_size=4)
    got = bm.allocate(7, tokens)
    assert len(got) == blocks
    assert bm.num_free == 16 - blocks
    bm.check_invariants()
    bm.release(7)
    assert bm.num_free == 16
    assert bm.refcount == {} and bm.tables == {}
    bm.check_invariants()


def test_append_allocates_on_boundary():
    bm = BlockManager(8, block_size=4)
    bm.allocate(1, 4)
    assert len(bm.tables[1]) == 1
    bm.append_tokens(1, 1)          # crosses into block 2
    assert len(bm.tables[1]) == 2
    bm.append_tokens(1, 3)          # fills block 2
    assert len(bm.tables[1]) == 2
    bm.check_invariants()


def test_out_of_blocks_raises():
    bm = BlockManager(2, block_size=4)
    bm.allocate(1, 8)
    with pytest.raises(OutOfBlocks):
        bm.allocate(2, 1)


def test_expand_contract_cycle():
    bm = BlockManager(8, block_size=4)
    bm.allocate(1, 32)  # all 8 blocks
    assert bm.num_free == 0
    start, end = bm.expand(4)
    assert (start, end) == (8, 12)
    assert bm.num_free == 4
    bm.allocate(2, 16)  # uses the extended region
    used_high = [b for b in bm.tables[2] if b >= bm.boundary]
    assert used_high, "expansion blocks should be used"
    bm.release(1)       # free the low region
    plan = bm.plan_contraction()
    assert plan is not None
    assert sorted(plan.src) == sorted(used_high)
    assert all(b < bm.boundary for b in plan.dst)
    bm.commit_contraction(plan)
    bm.check_invariants()
    assert bm.total_blocks == bm.base_blocks
    assert all(b < bm.boundary for t in bm.tables.values() for b in t)


@pytest.mark.parametrize("seed", range(8))
def test_invariants_under_seeded_random_ops(seed):
    """I1/I2: refcounts and free list stay consistent under arbitrary op
    sequences including expansion/contraction (seeded plain-pytest
    equivalent of the hypothesis property)."""
    rng = np.random.default_rng(seed)
    bm = BlockManager(16, block_size=4)
    live = {}
    next_id = 0
    expanded = False
    for _ in range(80):
        kind = int(rng.integers(0, 4))
        arg = int(rng.integers(1, 31))
        try:
            if kind == 0:  # allocate
                bm.allocate(next_id, arg)
                live[next_id] = arg
                next_id += 1
            elif kind == 1 and live:  # append
                sid = int(rng.choice(list(live)))
                bm.append_tokens(sid, arg % 8 + 1)
            elif kind == 2 and live:  # release
                sid = int(rng.choice(list(live)))
                bm.release(sid)
                del live[sid]
            elif kind == 3:
                if not expanded:
                    bm.expand(4)
                    expanded = True
                else:
                    plan = bm.plan_contraction()
                    if plan is not None:
                        bm.commit_contraction(plan)
                        expanded = False
        except OutOfBlocks:
            pass
        bm.check_invariants()


def test_migration_preserves_logical_contents():
    """I4: expansion -> writes into high blocks -> contraction + kernel
    migration leaves every sequence's gathered KV bit-identical."""
    rng = np.random.default_rng(0)
    L, bs, kh, hd = 2, 4, 2, 8
    bm = BlockManager(6, block_size=bs)
    pool = PhysicalKVPool(L, 6, bs, kh, hd, dtype=jnp.float32)

    bm.allocate(1, 20)          # 5 blocks
    bm.expand(4)
    pool.grow(4)
    bm.allocate(2, 12)          # 3 blocks: 1 low + high blocks

    writes = {}
    for sid, n in ((1, 20), (2, 12)):
        vals = rng.normal(size=(L, n, kh, hd)).astype(np.float32)
        pool.write_tokens(jnp.asarray(vals), jnp.asarray(2 * vals),
                          bm.tables[sid], 0)
        writes[sid] = vals

    before = {sid: pool.gather_sequence(bm.tables[sid], bm.lengths[sid])
              for sid in (1, 2)}
    bm.release(1)               # free low blocks so contraction has room
    plan = bm.plan_contraction()
    assert plan is not None and len(plan) > 0
    with ops.interpret_kernels():         # Pallas kernel (interpret mode)
        pool.migrate(plan)
    bm.commit_contraction(plan)
    pool.shrink(bm.base_blocks)
    bm.check_invariants()

    k_after, v_after = pool.gather_sequence(bm.tables[2], bm.lengths[2])
    np.testing.assert_array_equal(np.asarray(before[2][0]),
                                  np.asarray(k_after))
    np.testing.assert_array_equal(np.asarray(before[2][1]),
                                  np.asarray(v_after))
    assert all(b < bm.boundary for b in bm.tables[2])
