"""Walkthrough of the elastic memory manager (§6): offload -> pool expansion
-> KV writes into the extended region -> contraction with kernel-backed
block migration -> draft reload.  Real block tables + real array moves.

    PYTHONPATH=src python examples/elastic_memory_demo.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.serving.kv_cache import BlockManager, PhysicalKVPool  # noqa: E402
from repro.serving.memory_manager import ElasticMemoryManager  # noqa: E402


def main():
    L, nb, bs, kh, hd = 4, 24, 4, 2, 16
    bm = BlockManager(nb, bs)
    pool = PhysicalKVPool(L, nb, bs, kh, hd, dtype=jnp.float32)
    draft_blocks = 8

    mm = ElasticMemoryManager(
        bm, draft_blocks=draft_blocks, tau_low_frac=0.15, t_persist=2,
        offload_latency=0.004, reload_latency=0.004,
        migrate_fn=lambda plan: (pool.migrate(plan), 0.002)[1])

    print(f"pool: {nb} blocks x {bs} tokens; draft model worth "
          f"{draft_blocks} blocks; tau_low={mm.tau_low} blocks")

    # 1. load up the pool until pressure
    bm.allocate(1, 60)
    bm.allocate(2, 28)
    print(f"\n[load] free blocks = {bm.num_free} (< tau_low -> pressure)")

    # 2. speculation disabled + pressure persists -> offload & expand
    for step in range(3):
        mm.step(float(step), spec_disabled=True, waiting=4)
    print(f"[expand] draft_resident={mm.draft_resident} "
          f"total_blocks={bm.total_blocks} free={bm.num_free}")
    pool.grow(draft_blocks)

    # 3. new sequence lands in the extended region
    bm.allocate(3, 24)
    rng = np.random.default_rng(0)
    vals = rng.normal(size=(L, 24, kh, hd)).astype(np.float32)
    pool.write_tokens(jnp.asarray(vals), jnp.asarray(2 * vals),
                      bm.tables[3], 0)
    high = [b for b in bm.tables[3] if b >= bm.boundary]
    print(f"[write] seq3 occupies extended blocks {high}")
    before_k, before_v = pool.gather_sequence(bm.tables[3], 24)

    # 4. load drains -> contraction: plan, migrate (Pallas kernel), remap
    bm.release(1)
    mm.step(10.0, spec_disabled=True, waiting=0)
    pool.shrink(bm.base_blocks)
    print(f"[contract] total_blocks={bm.total_blocks} "
          f"draft_resident={mm.draft_resident}")
    print(f"  events: {[(e.kind, e.detail) for e in mm.events]}")

    # 5. verify logical consistency after physical moves
    after_k, after_v = pool.gather_sequence(bm.tables[3], 24)
    ok = (np.array_equal(np.asarray(before_k), np.asarray(after_k))
          and np.array_equal(np.asarray(before_v), np.asarray(after_v)))
    print(f"\nlogical KV identical across migration: {ok}")
    assert ok
    bm.check_invariants()
    print("allocator invariants hold")


if __name__ == "__main__":
    main()
