"""Continuous batching scheduler: admission, block gating, preemption,
deadline-aware prefill ordering."""
import pytest

from repro.serving.kv_cache import BlockManager
from repro.serving.request import Request, Sequence
from repro.serving.scheduler import ContinuousBatchingScheduler


def _reqs(n, prompt=8, out=8):
    return [Request(i, float(i) * 0.01, prompt, out) for i in range(n)]


def test_admission_respects_max_batch():
    bm = BlockManager(1000, 4)
    s = ContinuousBatchingScheduler(bm, max_batch=3)
    for r in _reqs(5):
        s.add_request(r)
    admitted = s.schedule()
    assert len(admitted) == 3
    assert s.num_waiting == 2


def test_admission_respects_blocks():
    bm = BlockManager(8, 4)   # 32 tokens capacity
    s = ContinuousBatchingScheduler(bm, max_batch=10, watermark_frac=0.0)
    for r in _reqs(5, prompt=11):  # 3 blocks each (11+1 tokens)
        s.add_request(r)
    admitted = s.schedule()
    assert len(admitted) == 2      # 3rd would need 3 blocks, only 2 left
    # finishing one frees blocks for the next
    s.finish(admitted[0])
    assert len(s.schedule()) == 1


def test_admission_blocked_below_watermark():
    """Admission stops when it would push free blocks under the watermark,
    even though the allocation itself would fit."""
    bm = BlockManager(100, 4)
    s = ContinuousBatchingScheduler(bm, max_batch=64, watermark_frac=0.1)
    # each request needs 3 blocks (9 tokensized: 8+1 -> 3 blocks of 4)
    for r in _reqs(40, prompt=8):
        s.add_request(r)
    admitted = s.schedule()
    # watermark = 10 blocks: admissions stop once free - 3 < 10
    assert 0 < len(admitted) < 40
    assert bm.num_free >= 10
    assert bm.num_free - 3 < 10   # the next one WOULD have crossed it
    assert s.num_waiting == 40 - len(admitted)
    # with the watermark off, the same state admits more
    s.watermark_frac = 0.0
    assert len(s.schedule()) > 0


def test_preempt_evicts_youngest_on_out_of_blocks():
    """When commit_tokens hits OutOfBlocks, the victim is the YOUNGEST
    running sequence (latest arrival), not the committing one."""
    bm = BlockManager(9, 4)
    s = ContinuousBatchingScheduler(bm, max_batch=8, watermark_frac=0.0)
    for r in _reqs(3, prompt=7):   # 2 blocks each -> 6 used, 3 free
        s.add_request(r)
    oldest, middle, youngest = s.schedule()
    assert youngest.request.arrival > middle.request.arrival
    # oldest grows by 12 tokens -> needs 3 new blocks, only 3 free: first
    # append succeeds; keep growing until eviction triggers
    for _ in range(4):
        ok = s.commit_tokens(oldest, 4)
        assert ok   # the committing sequence itself survives
        if youngest not in s.running:
            break
    assert youngest not in s.running          # youngest evicted first
    assert middle in s.running                # older survivor untouched
    assert oldest in s.running
    assert s.waiting[0] is youngest.request   # requeued at the FRONT
    bm.check_invariants()


def test_freed_blocks_reusable_same_step():
    """Blocks released by finish() are allocatable in the same scheduling
    step (no deferred reclamation)."""
    bm = BlockManager(4, 4)
    s = ContinuousBatchingScheduler(bm, max_batch=8, watermark_frac=0.0)
    for r in _reqs(2, prompt=7):   # 2 blocks each
        s.add_request(r)
    (a, b) = s.schedule()
    assert bm.num_free == 0
    s.add_request(_reqs(3, prompt=7)[2])
    assert s.schedule() == []      # pool exhausted, c cannot enter
    s.finish(a)                    # frees 2 blocks...
    admitted = s.schedule()        # ...immediately reusable
    assert len(admitted) == 1
    assert bm.num_free == 0
    bm.check_invariants()


# ---------------------------------------------------------------------------
# deadline-aware prefill admission (prefill_order="slo")
# ---------------------------------------------------------------------------


def _slo_sched(chunk=32, order="slo"):
    bm = BlockManager(1000, 4)
    return ContinuousBatchingScheduler(bm, max_batch=8, watermark_frac=0.0,
                                       chunk_tokens=chunk,
                                       prefill_order=order)


def test_slo_order_admits_earliest_deadline_first():
    """Under budget contention the tightest TTFT deadline wins admission,
    regardless of arrival order; deadline-free requests sort last."""
    s = _slo_sched(chunk=40)
    s.add_request(Request(0, 0.0, 40, 2, slo=None))      # no deadline
    s.add_request(Request(1, 0.1, 40, 2, slo=5.0))       # deadline 5.1
    s.add_request(Request(2, 0.2, 40, 2, slo=1.0))       # deadline 1.2 (!)
    batch = s.schedule_chunks()
    assert [c[0].req_id for c in batch.prefill_chunks] == [2]
    batch2 = s.schedule_chunks()
    assert [c[0].req_id for c in batch2.prefill_chunks][0] == 2  # continues
    # FIFO among the rest once 2 finishes its prompt
    ids = [c[0].req_id for c in batch2.prefill_chunks]
    assert ids in ([2], [2, 1])


def test_slo_order_fifo_among_equal_deadlines():
    s = _slo_sched(chunk=16)
    s.add_request(Request(0, 0.0, 16, 2, slo=1.0))
    s.add_request(Request(1, 0.0, 16, 2, slo=1.0))       # same deadline
    batch = s.schedule_chunks()
    assert [c[0].req_id for c in batch.prefill_chunks] == [0]


def test_fifo_order_is_default_and_unchanged():
    s = _slo_sched(chunk=40, order="fifo")
    s.add_request(Request(0, 0.0, 40, 2, slo=None))
    s.add_request(Request(1, 0.1, 40, 2, slo=0.1))
    batch = s.schedule_chunks()
    assert [c[0].req_id for c in batch.prefill_chunks] == [0]


def test_slo_order_keeps_midprefill_progress_guarantee():
    """A running mid-prefill sequence is still served before ANY admission,
    even when a newer arrival has a tighter deadline (no starvation)."""
    s = _slo_sched(chunk=16)
    s.add_request(Request(0, 0.0, 64, 2, slo=10.0))
    b = s.schedule_chunks()
    assert [c[0].req_id for c in b.prefill_chunks] == [0]
    for seq, n in b.prefill_chunks:
        seq.prefilled += n
    s.add_request(Request(1, 0.5, 8, 2, slo=0.1))        # urgent newcomer
    b2 = s.schedule_chunks()
    ids = [c[0].req_id for c in b2.prefill_chunks]
    assert ids[0] == 0                                   # continue first
    assert b2.prefill_chunks[0][1] == 16


def test_invalid_prefill_order_rejected():
    bm = BlockManager(8, 4)
    with pytest.raises(ValueError):
        ContinuousBatchingScheduler(bm, chunk_tokens=8,
                                    prefill_order="deadline")


def test_preemption_recompute():
    bm = BlockManager(6, 4)
    s = ContinuousBatchingScheduler(bm, max_batch=4, watermark_frac=0.0)
    for r in _reqs(2, prompt=7):   # 2 blocks each
        s.add_request(r)
    a, b = s.schedule()
    # grow sequence a until the pool is exhausted -> b preempted (youngest)
    ok = True
    for _ in range(20):
        ok = s.commit_tokens(a, 4)
        if b not in s.running:
            break
    assert b not in s.running
    assert s.num_waiting == 1     # b requeued for recompute
    assert a in s.running
    bm.check_invariants()


# ---------------------------------------------------------------------------
# running sequences compare by identity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("leave", ["finish", "preempt"])
def test_equal_sequences_are_distinct_running_members(leave):
    """Two sequences with equal field values (a request's stale and its
    re-admitted lifecycle object, say) are distinct members of ``running``;
    ``finish`` and ``preempt`` remove exactly the object passed."""
    bm = BlockManager(16, 4)
    s = ContinuousBatchingScheduler(bm, max_batch=8, watermark_frac=0.0)
    a = Sequence(Request(0, 0.0, 8, 8))
    b = Sequence(Request(0, 0.0, 8, 8))
    assert a is not b and a != b and len({a, b}) == 2
    s.running.extend([a, b])
    assert a in s.running and b in s.running
    getattr(s, leave)(b)
    assert len(s.running) == 1 and s.running[0] is a
    getattr(s, leave)(a)
    assert s.running == []


class _ReleaseLog:
    """Backend stub for ``_commit_decode``: records what it releases."""

    def __init__(self):
        self.released = []

    def release(self, seq):
        self.released.append(seq)


def test_commit_decode_compares_no_values(monkeypatch):
    """A 64-row gamma-0 commit finds its rows in ``running`` by identity:
    no ``Request`` is compared by value.  In the same commit the oldest
    row's growth preempts the youngest row, which is then skipped, and
    rows at their output limit finish and are released."""
    from repro.serving.engine import ServingEngine

    bm = BlockManager(128, 4)
    s = ContinuousBatchingScheduler(bm, max_batch=64, watermark_frac=0.0)
    at_limit = range(10, 20)
    for i in range(64):
        # row 0 fills its two blocks, every other row leaves one slot free:
        # the 128-block pool is full and only row 0's commit needs a block
        s.add_request(Request(i, i * 0.01, 7 if i == 0 else 6,
                              1 if i in at_limit else 100))
    seqs = s.schedule()
    assert len(seqs) == 64 and bm.num_free == 0
    backend = _ReleaseLog()
    eng = ServingEngine(backend, s, policy=None)

    def no_value_compare(self, other):
        raise AssertionError("Request compared by value")

    with monkeypatch.context() as mp:
        mp.setattr(Request, "__eq__", no_value_compare)
        finished, clipped = eng._commit_decode(seqs, [1] * 64, 0)

    youngest = seqs[63]
    assert (finished, clipped) == (len(at_limit), 0)
    assert youngest not in s.running and youngest.generated == 0
    assert s.waiting[0] is youngest.request
    assert seqs[0].generated == 1 and len(bm.tables[0]) == 3
    assert backend.released == [seqs[i] for i in at_limit]
    assert [r.req_id for r in eng.metrics.requests] == list(at_limit)
    assert all(seqs[i] not in s.running and i not in bm.tables
               for i in at_limit)
    assert len(s.running) == 64 - 1 - len(at_limit)
    assert all(q.generated == 1 for q in s.running)
    bm.check_invariants()
