"""Real-execution backends: actual JAX models behind the serving engine.

Two implementations share the Backend protocol:

* :class:`RealBackend` — the paged-KV runtime (production path for
  attention-family models).  ``(L, num_blocks + 1, block_size, KH * hd)``
  key/value pools per model are allocated ONCE and driven by the
  :class:`BlockManager` block tables: admission, decode, speculative
  verification, chunked prefill, eviction and completion touch only int32
  tables and sampled tokens — the cache tensors never travel and are never
  gathered, scattered or re-bucketed.  One multi-query paged-attention
  kernel (compiled Pallas on a TPU, its jnp oracle elsewhere — see
  ``kernels.ops``) serves plain decode (T=1), speculative verify
  (T=gamma+1), prefill and chunked-prefill appends (T=chunk), so
  ``hybrid_step`` runs the chunked scheduler's mixed chunk+decode batches
  on real execution end-to-end.

* :class:`DenseSlotBackend` — the legacy dense slot-cache implementation
  (whole-cache gather/scatter per step, per-sequence Python prefill loop),
  kept for the SSM/hybrid/encdec families whose recurrent state is O(1)
  and not paged, and as the baseline for the dense-vs-paged equivalence
  tests and ``--only backend`` benchmarks.

:func:`make_real_backend` picks the implementation per model family;
:func:`build_real_engine` assembles the whole real serving tier (backend,
scheduler, planner, elastic memory manager) for one configuration.

Latencies are wall-clock (block_until_ready) — this is what the planner
learns from on this tier, and what the C_switch profiler measures.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.spec_decode import (make_ar_step, make_paged_ar_step,
                                make_paged_spec_step, make_spec_step)
from ..kernels import paged_attention as paged_mod
from ..models.registry import ModelAPI
from .engine import StepOutcome
from .kv_cache import BlockManager, OutOfBlocks
from .observability import span
from .paged_runtime import PagedKVRuntime, bucket_size, num_blocks_for
from .request import Sequence



def _bucket(n: int) -> int:
    return bucket_size(n)


def _note_pages(tr, sp, tables: np.ndarray, ends, read: int,
                block_size: int) -> None:
    """Sizes of one decode call, on its span and into the recorder's page
    counters: the pages holding a key some real row attends (row ``i``
    attends the positions below ``ends[i]``), the pages the target's
    paged-attention kernel copies (``read``: each row's pages up to its
    last attended key, padded rows included) and the pages of the padded
    table."""
    valid = sum(-(-int(e) // block_size) for e in ends)
    sp.set(rows=len(ends), padded_rows=tables.shape[0], width=tables.shape[1],
           pages_valid=valid, pages_walked=read, table_pages=int(tables.size))
    reg = tr.registry
    reg.counter("kv_pages_valid_total",
                "KV pages holding a key a decode row attends").inc(valid)
    reg.counter("kv_pages_walked_total",
                "KV pages the decode kernel reads").inc(read)


def make_real_backend(target: ModelAPI, draft: ModelAPI, **kw):
    """Paged runtime when both models have a paged-KV path (attention
    families); dense slot caches otherwise (SSM/hybrid/encdec state is O(1)
    per sequence and lives in fixed slots)."""
    if target.supports_paged and draft.supports_paged:
        return RealBackend(target, draft, **kw)
    for k in ("block_manager", "num_blocks", "block_size"):
        kw.pop(k, None)
    return DenseSlotBackend(target, draft, **kw)


# ---------------------------------------------------------------------------
# Paged-KV backend
# ---------------------------------------------------------------------------


class RealBackend:
    """Zero-copy continuous batching over paged KV pools.

    When ``block_manager`` is the scheduler's own instance, the scheduler's
    logical admission decisions and the physical pool are one and the same
    object (the intended wiring — see :func:`build_real_engine`).  Without
    one, a private BlockManager sized for ``max_batch x max_seq`` is created
    and mirrored internally.
    """

    def __init__(self, target: ModelAPI, draft: ModelAPI, *,
                 max_batch: int = 8, max_seq: int = 256, seed: int = 0,
                 sampling: str = "greedy", temperature: float = 1.0,
                 block_manager: Optional[BlockManager] = None,
                 block_size: int = 8, num_blocks: Optional[int] = None):
        if not (target.supports_paged and draft.supports_paged):
            raise NotImplementedError(
                "RealBackend is the paged-KV runtime; use make_real_backend "
                "(or DenseSlotBackend) for SSM/hybrid/encdec families")
        self.target = target
        self.draft = draft
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.sampling = sampling
        self.key = jax.random.PRNGKey(seed)

        k1, k2 = jax.random.split(jax.random.PRNGKey(seed + 1))
        # jitted init draws each weight straight into its dtype: eager init
        # would hold a float32 copy of the largest stacked weight beside it
        self.tparams = jax.jit(target.init)(k1)
        self.dparams = jax.jit(draft.init)(k2)
        self.dparams_host: Optional[dict] = None  # offloaded copy

        if block_manager is None:
            if num_blocks is None:
                num_blocks = (-(-max_batch * max_seq // block_size)
                              + 2 * max_batch)
            block_manager = BlockManager(num_blocks, block_size)
            self._owns_bm = True
        else:
            self._owns_bm = False
        self.bm = block_manager
        self.tkv = PagedKVRuntime(target, self.bm)
        self.dkv = PagedKVRuntime(draft, self.bm)

        self.last_token: Dict[int, int] = {}
        self.tokens_out: Dict[int, List[int]] = {}
        # wall-mode flight recorder (ServingEngine.attach_trace), or None:
        # host spans inside each call (prepare / device_wait / readback)
        self.trace = None

        # page donation keeps the pools in place on accelerators; CPU jax
        # cannot donate and would only warn
        donate = jax.default_backend() != "cpu"
        spec = make_paged_spec_step(target, draft, sampling=sampling,
                                    temperature=temperature)
        self._spec_jit = jax.jit(spec, static_argnames=("gamma",),
                                 donate_argnums=(3, 4) if donate else ())
        ar = make_paged_ar_step(target, sampling=sampling,
                                temperature=temperature)
        self._ar_jit = jax.jit(ar, donate_argnums=(2,) if donate else ())

        @jax.named_scope("extend")
        def _extend_target(key, params, pages, tokens, tables, start, valid):
            """Multi-token extension + next-token sample at each row's last
            valid position (batched prefill / chunked-prefill appends fused
            with T=1 decode rows)."""
            logits, pages = target.decode_step_paged(
                params, pages, tokens, tables, start, valid, last_only=True)
            lg = logits[:, 0] / temperature
            if sampling == "greedy":
                nxt = jnp.argmax(lg, axis=-1)
            else:
                nxt = jax.random.categorical(key, lg)
            return nxt, pages

        @jax.named_scope("extend")
        def _extend_draft(params, pages, tokens, tables, start, valid):
            _, pages = draft.decode_step_paged(params, pages, tokens, tables,
                                               start, valid, last_only=True)
            return pages

        self._extend_t = jax.jit(_extend_target,
                                 donate_argnums=(2,) if donate else ())
        self._extend_d = jax.jit(_extend_draft,
                                 donate_argnums=(1,) if donate else ())

    # ------------------------------------------------------------------
    def _pages_read(self, tables, lengths, n_queries: int) -> int:
        """Pages the target's paged-attention kernel copies in one layer
        of a call extending every row by ``n_queries`` to ``lengths``."""
        c = self.target.cfg
        return paged_mod.pages_read(lengths, n_queries, c.num_heads,
                                    c.resolved_head_dim,
                                    self.tkv.pages["k_pages"],
                                    tables.shape[1])

    def _next_key(self):
        self.key, k = jax.random.split(self.key)
        return k

    def offload_draft(self) -> None:
        self.dparams_host = jax.tree.map(np.asarray, self.dparams)
        self.dparams = None

    def reload_draft(self) -> None:
        assert self.dparams_host is not None
        self.dparams = jax.tree.map(jnp.asarray, self.dparams_host)

    @property
    def draft_resident(self) -> bool:
        return self.dparams is not None

    # ------------------------------------------------------------------
    # block-table bookkeeping (int32 only — the pages only move for CoW
    # forks and elastic migration, both batched block-migration launches)
    # ------------------------------------------------------------------
    def _ensure_alloc(self, req_id: int, tokens: int) -> None:
        if req_id in self.bm.tables:
            self.bm.ensure_capacity(req_id, tokens)
        else:
            # private BlockManager: mirror the scheduler's admission
            self.bm.allocate(req_id, tokens)

    def on_admit(self, seq: Sequence) -> None:
        """A sequence admitted with a cached prefix starts with that many
        tokens already materialised — in BOTH pools (only draft-covered
        prefixes are ever registered, see scheduler.note_prefill_progress)."""
        self.tkv.ctx[seq.req_id] = seq.prefilled
        self.dkv.ctx[seq.req_id] = seq.prefilled

    def apply_host_transfers(self) -> None:
        """Drain the BlockManager's host-tier queues: gather freshly
        spilled blocks' pages (BOTH pools) into their ``HostKVStore``
        records, then scatter queued restores back into their target device
        blocks — spills strictly first, so a block spilled and re-matched
        in the same scheduling round restores the payload captured here.
        Runs BEFORE CoW copies and step writes (``_apply_pending_copies``)
        so eviction-time content is read before anything overwrites it."""
        hs = getattr(self.bm, "host_store", None)
        if hs is None:
            return
        spills = self.bm.drain_pending_spills()
        if spills:
            t0 = time.perf_counter()
            ids = [b for b, _ in spills]
            tpay = self.tkv.spill_blocks(ids)
            dpay = self.dkv.spill_blocks(ids)
            for i, (_, h) in enumerate(spills):
                rec = hs.records.get(h)
                if rec is None:
                    continue          # host LRU dropped it before the copy
                rec.data = {f"t:{k}": v[:, i] for k, v in tpay.items()}
                rec.data.update(
                    {f"d:{k}": v[:, i] for k, v in dpay.items()})
                hs.seal(h)  # re-stamp the checksum over the filled pages
                hs.stats["spilled_blocks"] += 1
            hs.stats["spill_s"] += time.perf_counter() - t0
        restores = self.bm.drain_pending_restores()
        if restores:
            t0 = time.perf_counter()
            # a queued restore's record is pinned from match to drain, so
            # it cannot have been evicted from the host tier in between, and
            # the fault injector never corrupts pinned records — so a
            # checksum mismatch here is real memory corruption, not noise
            assert all(hs.verify(h) for h, _ in restores), \
                "pinned host record fails its checksum at restore drain"
            recs = [(b, hs.take(h)) for h, b in restores]
            assert all(r is not None and r.data for _, r in recs), \
                "pinned host record lost before its restore drained"
            ids = [b for b, _ in recs]
            self.tkv.restore_blocks(ids, {
                k: np.stack([r.data[f"t:{k}"] for _, r in recs], axis=1)
                for k in self.tkv.pages})
            self.dkv.restore_blocks(ids, {
                k: np.stack([r.data[f"d:{k}"] for _, r in recs], axis=1)
                for k in self.dkv.pages})
            jax.block_until_ready(self.tkv.pages["k_pages"])
            jax.block_until_ready(self.dkv.pages["k_pages"])
            hs.stats["restore_s"] += time.perf_counter() - t0

    def _apply_pending_copies(self) -> None:
        """Execute the BlockManager's queued CoW forks on-device (one
        batched block-migration launch per pool) BEFORE this step's writes,
        so a privatised block carries its shared content when written.
        Host-tier spills/restores drain first: a spill must read its
        block's pages before a CoW copy or step write can touch them."""
        self.apply_host_transfers()
        copies = self.bm.drain_pending_copies()
        if not copies:
            return
        src = [c[0] for c in copies]
        dst = [c[1] for c in copies]
        self.tkv.apply_copies(src, dst)
        self.dkv.apply_copies(src, dst)

    def reserve(self, seqs: List[Sequence], gamma: int) -> List[Sequence]:
        """Grow block tables to cover this step's gamma+1 KV writes BEFORE
        executing, so a paged write can never land in another sequence's
        blocks; any shared block the write range covers is privatised first
        (copy-on-write).  Returns the sequences whose reservation failed —
        the engine preempts those (recompute policy) instead of running
        them."""
        failed = []
        for s in seqs:
            ctx = self.tkv.ctx.get(s.req_id, 0)
            need = ctx + gamma + 1
            try:
                if self.bm.prefix_caching and s.req_id in self.bm.tables:
                    self.bm.fork_for_write(s.req_id, ctx, need)
                self._ensure_alloc(s.req_id, need)
            except OutOfBlocks:
                failed.append(s)
        return failed

    # ------------------------------------------------------------------
    # elastic physical pool (memory-manager hooks, §6.3/6.4 on real tier)
    # ------------------------------------------------------------------
    def grow_pools(self, extra_blocks: int) -> None:
        """§6.3: extend both physical paged pools in lockstep with
        ``BlockManager.expand`` (ElasticMemoryManager ``grow_fn``)."""
        self.tkv.grow(extra_blocks)
        self.dkv.grow(extra_blocks)

    def shrink_pools(self, to_blocks: Optional[int] = None) -> None:
        """§6.4 step 5: trim both pools after the logical contraction
        committed (ElasticMemoryManager ``shrink_fn``)."""
        nb = self.bm.base_blocks if to_blocks is None else to_blocks
        self.tkv.shrink(nb)
        self.dkv.shrink(nb)

    def migrate_pools(self, plan) -> float:
        """§6.4 step 3: execute the contraction's block moves on both pools
        (ElasticMemoryManager ``migrate_fn``); returns wall-clock seconds.
        Contraction-time spills flush first — the spilled high blocks'
        pages must be captured before migration reuses their below-boundary
        targets and before ``shrink_pools`` trims the high region."""
        self.apply_host_transfers()
        t0 = time.perf_counter()
        self.tkv.apply_plan(plan)
        self.dkv.apply_plan(plan)
        jax.block_until_ready(self.tkv.pages["k_pages"])
        jax.block_until_ready(self.dkv.pages["k_pages"])
        return time.perf_counter() - t0

    def _fill_rows(self, rows: List[Tuple[Sequence, List[int], int, int]]
                   ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """rows of (seq, tokens, start, n_valid) -> padded step operands."""
        Bb = _bucket(len(rows))
        Tb = _bucket(max(len(r[1]) for r in rows))
        tokens = np.zeros((Bb, Tb), np.int32)
        start = np.zeros((Bb,), np.int32)
        valid = np.zeros((Bb,), np.int32)
        for i, (_, toks, c, nv) in enumerate(rows):
            tokens[i, :len(toks)] = toks
            start[i] = c
            valid[i] = nv
        return tokens, start, valid, Bb

    # ------------------------------------------------------------------
    def prefill(self, seqs: List[Sequence], *, with_draft: bool) -> float:
        """Batched prefill: every admitted prompt in ONE padded extension
        call (start=0), its KV scattered straight into the paged pool."""
        tr = self.trace
        t0 = time.perf_counter()
        with span(tr, "nightjar.prefill") as sp:
            with span(tr, "nightjar.prepare"):
                rows = []
                for s in seqs:
                    if self._owns_bm and s.req_id in self.bm.tables:
                        self.bm.release(s.req_id)  # recompute after preemption
                    self._ensure_alloc(s.req_id, s.request.prompt_len + 1)
                    toks = list(s.request.prompt_tokens)
                    rows.append((s, toks, 0, len(toks)))
                self._apply_pending_copies()
                tokens, start, valid, Bb = self._fill_rows(rows)
                tables, _ = self.tkv.batch_tables(seqs, Bb)
            if sp is not None:
                sp.set(rows=len(seqs), padded_rows=Bb, width=tables.shape[1])
                tr.registry.counter(
                    "prefill_tokens_total",
                    "prompt tokens prefilled").inc(int(valid.sum()))
            with span(tr, "nightjar.device_wait", program="extend_target"):
                nxt, self.tkv.pages = self._extend_t(
                    self._next_key(), self.tparams, self.tkv.pages, tokens,
                    tables, start, valid)
                jax.block_until_ready(nxt)
            with span(tr, "nightjar.readback"):
                nxt = np.asarray(nxt)
            do_draft = with_draft and self.draft_resident
            if do_draft:
                with span(tr, "nightjar.device_wait", program="extend_draft"):
                    self.dkv.pages = self._extend_d(
                        self.dparams, self.dkv.pages, tokens, tables, start,
                        valid)
                    jax.block_until_ready(self.dkv.pages)
            with span(tr, "nightjar.readback"):
                for i, s in enumerate(seqs):
                    P = s.request.prompt_len
                    self.tkv.ctx[s.req_id] = P
                    self.tokens_out[s.req_id] = [int(nxt[i])]
                    self.last_token[s.req_id] = int(nxt[i])
                    # first token counted at the first decode commit
                    s.generated = 0
                    if do_draft:
                        self.dkv.ctx[s.req_id] = P
                        s.delta = 0
                    else:
                        self.dkv.ctx[s.req_id] = 0
                        s.delta = P
        return time.perf_counter() - t0

    def draft_catchup(self, seqs: List[Sequence]) -> float:
        """Re-prefill the draft pool for sequences whose draft state lags
        (the physical C_switch cost) — one batched paged extension."""
        if not self.draft_resident:
            return 0.0
        tr = self.trace
        with span(tr, "nightjar.catchup") as sp:
            with span(tr, "nightjar.prepare"):
                rows = []
                for s in seqs:
                    ctx = self.tkv.ctx.get(s.req_id)
                    if ctx is None:
                        continue
                    dctx = self.dkv.ctx.get(s.req_id, 0)
                    if dctx > ctx:
                        # stale (preempt-and-recompute): full re-prefill
                        dctx = 0
                    if dctx >= ctx:
                        continue
                    stream = (list(s.request.prompt_tokens)
                              + self.tokens_out.get(s.req_id, []))
                    rows.append((s, stream[dctx:ctx], dctx, ctx - dctx))
                if not rows:
                    return 0.0
                t0 = time.perf_counter()
                tokens, start, valid, Bb = self._fill_rows(rows)
                tables, _ = self.dkv.batch_tables([r[0] for r in rows], Bb)
            if sp is not None:
                sp.set(rows=len(rows), padded_rows=Bb, width=tables.shape[1])
            with span(tr, "nightjar.device_wait", program="extend_draft"):
                self.dkv.pages = self._extend_d(self.dparams, self.dkv.pages,
                                                tokens, tables, start, valid)
                jax.block_until_ready(self.dkv.pages)
            with span(tr, "nightjar.readback"):
                for s, _, _, _ in rows:
                    self.dkv.ctx[s.req_id] = self.tkv.ctx[s.req_id]
                    s.delta = 0
        return time.perf_counter() - t0

    # ------------------------------------------------------------------
    def hybrid_step(self, chunks, decode: List[Sequence], gamma: int,
                    *, with_draft: bool) -> StepOutcome:
        """One fused mixed step on real execution: prefill chunks (ragged
        multi-token appends into freshly grown blocks) batched together with
        the T=1 decode rows in a single padded extension call."""
        if not chunks:
            if not decode:
                return StepOutcome(n_committed=[], latency=0.0)
            return self.step(decode, gamma)
        assert gamma == 0, "speculation is disabled while chunks are in flight"
        tr = self.trace
        with span(tr, "nightjar.decode") as sp:
            with span(tr, "nightjar.prepare"):
                if self.reserve(decode, 0):
                    raise OutOfBlocks("decode rows not reserved — engine "
                                      "must preempt before hybrid_step")
                rows = []
                for s, n in chunks:
                    # authoritative (survives preempt-and-recompute)
                    c = s.prefilled
                    self.tkv.ctx[s.req_id] = c
                    if c == 0:
                        self.dkv.ctx[s.req_id] = 0  # fresh / restarted
                    self._ensure_alloc(s.req_id, c + n)
                    toks = list(s.request.prompt_tokens[c:c + n])
                    rows.append((s, toks, c, n))
                n_chunks = len(rows)
                for s in decode:
                    rows.append((s, [self.last_token[s.req_id]],
                                 self.tkv.ctx[s.req_id], 1))
                t0 = time.perf_counter()
                # CoW forks queued at schedule/reserve time execute BEFORE
                # the step's writes (their cost is real step latency)
                self._apply_pending_copies()
                tokens, start, valid, Bb = self._fill_rows(rows)
                tables, _ = self.tkv.batch_tables([r[0] for r in rows], Bb)
            if sp is not None:
                _note_pages(tr, sp, tables, (start + valid)[:len(rows)],
                            self._pages_read(tables, start + tokens.shape[1],
                                             tokens.shape[1]),
                            self.bm.block_size)
            with span(tr, "nightjar.device_wait", program="extend_target"):
                nxt, self.tkv.pages = self._extend_t(
                    self._next_key(), self.tparams, self.tkv.pages, tokens,
                    tables, start, valid)
                jax.block_until_ready(nxt)
            with span(tr, "nightjar.readback"):
                nxt = np.asarray(nxt)

            do_draft = with_draft and self.draft_resident
            if do_draft:
                # the draft consumes the same chunk stream to keep its KV
                # current (decode rows stay out: gamma=0 commits are charged
                # to delta)
                drows = [r for r in rows[:n_chunks]
                         if self.dkv.ctx.get(r[0].req_id, 0) == r[2]]
                if drows:
                    with span(tr, "nightjar.prepare"):
                        dtokens, dstart, dvalid, Db = self._fill_rows(drows)
                        dtables, _ = self.dkv.batch_tables(
                            [r[0] for r in drows], Db)
                    with span(tr, "nightjar.device_wait",
                              program="extend_draft"):
                        self.dkv.pages = self._extend_d(
                            self.dparams, self.dkv.pages, dtokens, dtables,
                            dstart, dvalid)
                        jax.block_until_ready(self.dkv.pages)
                    for s, _, c, n in drows:
                        self.dkv.ctx[s.req_id] = c + n
            latency = time.perf_counter() - t0

            with span(tr, "nightjar.readback"):
                for i, (s, _, c, n) in enumerate(rows):
                    if i < n_chunks:
                        self.tkv.ctx[s.req_id] = c + n
                        if c + n == s.request.prompt_len:
                            # final chunk: the sampled token is the first
                            # output x_N
                            self.tokens_out[s.req_id] = [int(nxt[i])]
                            self.last_token[s.req_id] = int(nxt[i])
                    else:
                        self.tokens_out[s.req_id].append(int(nxt[i]))
                        self.last_token[s.req_id] = int(nxt[i])
                        self.tkv.ctx[s.req_id] = c + 1
        return StepOutcome(n_committed=[1] * len(decode), latency=latency)

    # ------------------------------------------------------------------
    def step(self, seqs: List[Sequence], gamma: int) -> StepOutcome:
        tr = self.trace
        with span(tr, "nightjar.decode", gamma=gamma) as sp:
            with span(tr, "nightjar.prepare"):
                if self.reserve(seqs, gamma):
                    raise OutOfBlocks("decode batch not reserved — engine "
                                      "must preempt before step")
                self._apply_pending_copies()
                n = len(seqs)
                Bb = _bucket(n)
                tables, lengths = self.tkv.batch_tables(seqs, Bb)
                last = np.zeros((Bb,), np.int32)
                for i, s in enumerate(seqs):
                    last[i] = self.last_token[s.req_id]
            if sp is not None:
                # the step writes, then attends, gamma + 1 positions per row
                _note_pages(tr, sp, tables, lengths[:n] + gamma + 1,
                            self._pages_read(tables, lengths + gamma + 1,
                                             gamma + 1),
                            self.bm.block_size)

            t0 = time.perf_counter()
            if gamma == 0:
                with span(tr, "nightjar.device_wait", program="decode"):
                    nxt, self.tkv.pages = self._ar_jit(
                        self._next_key(), self.tparams, self.tkv.pages,
                        tables, lengths, last)
                    jax.block_until_ready(nxt)
                with span(tr, "nightjar.readback"):
                    nxt = np.asarray(nxt)
                    latency = time.perf_counter() - t0
                    n_committed = []
                    for i, s in enumerate(seqs):
                        self.tokens_out[s.req_id].append(int(nxt[i]))
                        self.last_token[s.req_id] = int(nxt[i])
                        self.tkv.ctx[s.req_id] += 1
                        n_committed.append(1)
                return StepOutcome(n_committed=n_committed, latency=latency)

            with span(tr, "nightjar.device_wait", program="spec"):
                res = self._spec_jit(self._next_key(), self.tparams,
                                     self.dparams, self.tkv.pages,
                                     self.dkv.pages, tables, lengths, last,
                                     gamma=gamma)
                jax.block_until_ready(res.n_accepted)
            latency = time.perf_counter() - t0
            with span(tr, "nightjar.readback"):
                self.tkv.pages, self.dkv.pages = res.tcache, res.dcache
                toks = np.asarray(res.tokens)
                n_acc = np.asarray(res.n_accepted)
                last_np = np.asarray(res.last_token)
                n_committed = []
                for i, s in enumerate(seqs):
                    committed = [int(t) for t in toks[i] if t >= 0]
                    self.tokens_out[s.req_id].extend(committed)
                    self.last_token[s.req_id] = int(last_np[i])
                    n_keep = int(n_acc[i]) + 1
                    self.tkv.ctx[s.req_id] += n_keep
                    self.dkv.ctx[s.req_id] = self.tkv.ctx[s.req_id]
                    n_committed.append(n_keep)
        return StepOutcome(n_committed=n_committed, latency=latency)

    # ------------------------------------------------------------------
    # disaggregated prefill/decode handoff (physical KV migration)
    # ------------------------------------------------------------------
    def export_handoff(self, seq: Sequence) -> dict:
        """Capture a fully-prefilled sequence's physical state for migration
        to a decode replica: both pools' block payloads (the same batched
        gather the host-offload spill path uses) plus the sampler
        bookkeeping the decode loop needs (materialised lengths, the first
        sampled output token, last-token id).  Called by the engine BEFORE
        it releases the source block tables."""
        rid = seq.req_id
        table = list(self.bm.tables.get(rid, ()))
        out = {
            "ctx": int(self.tkv.ctx.get(rid, 0)),
            "dctx": int(self.dkv.ctx.get(rid, 0)),
            "tokens_out": list(self.tokens_out.get(rid, [])),
            "last_token": self.last_token.get(rid),
            "n_blocks": len(table),
        }
        if table:
            out["tkv"] = self.tkv.spill_blocks(table)
            out["dkv"] = self.dkv.spill_blocks(table)
        return out

    def import_handoff(self, seq: Sequence, payload: dict) -> None:
        """Adopt a migrated sequence: scatter the exported block payloads
        into this replica's freshly allocated blocks (same data movement as
        ``restore_blocks`` on the host-offload path) and rebuild the decode
        bookkeeping, so the next decode step continues byte-identically to
        never having moved."""
        kv = payload.get("kv")
        if not kv:
            return
        rid = seq.req_id
        ctx = int(kv.get("ctx", 0))
        self._ensure_alloc(rid, max(ctx, 1))
        table = list(self.bm.tables.get(rid, ()))
        # the destination table covers exactly the materialised ctx tokens;
        # a source tail block past ctx (allocation rounding) is never read,
        # so restoring the common prefix is sufficient
        n = min(len(table), int(kv.get("n_blocks", 0)))
        if n:
            ids = table[:n]
            self.tkv.restore_blocks(
                ids, {k: v[:, :n] for k, v in kv["tkv"].items()})
            self.dkv.restore_blocks(
                ids, {k: v[:, :n] for k, v in kv["dkv"].items()})
        self.tkv.ctx[rid] = ctx
        self.dkv.ctx[rid] = int(kv.get("dctx", 0))
        self.tokens_out[rid] = list(kv.get("tokens_out", []))
        if kv.get("last_token") is not None:
            self.last_token[rid] = int(kv["last_token"])

    # ------------------------------------------------------------------
    def release(self, seq: Sequence) -> None:
        self.tkv.ctx.pop(seq.req_id, None)
        self.dkv.ctx.pop(seq.req_id, None)
        self.last_token.pop(seq.req_id, None)
        # engine flow releases through scheduler.finish first, leaving this a
        # no-op there; direct backend users (benchmarks) free their blocks
        if seq.req_id in self.bm.tables:
            self.bm.release(seq.req_id)

    def output_tokens(self, req_id: int) -> List[int]:
        return self.tokens_out.get(req_id, [])


# ---------------------------------------------------------------------------
# The real serving tier, assembled
# ---------------------------------------------------------------------------

# KV block size of the real tier: the bf16 sublane tile
BLOCK_SIZE = 16
# positions per slot of DenseSlotBackend (SSM/hybrid/encdec pairs)
DENSE_MAX_SEQ = 256


def build_real_engine(arch: str, policy: str = "nightjar", *,
                      reduced: bool = False, max_batch: int = 8,
                      gamma_max: int = 5, chunk_tokens: Optional[int] = None,
                      prefix_caching: bool = False,
                      prefill_order: str = "fifo", kv_offload: bool = False,
                      offload: bool = True, seed: int = 0):
    """The real serving tier for the registered pair ``arch``: the backend
    :func:`make_real_backend` picks for its family over one
    :class:`BlockManager` shared with the scheduler, the ``policy`` planner
    and, with ``offload``, the :class:`ElasticMemoryManager` wired to the
    physical pools.  For a paged pair (:class:`RealBackend`) the pool is
    sized from the roofline HBM budget of one TPU v5e; for a dense-slot
    pair it meters admission over the slots' ``max_batch x DENSE_MAX_SEQ``
    positions, and there is no elastic memory manager.

    The configuration runs as registered; ``reduced`` swaps in
    ``configs.reduced`` of both models (CPU runs and tests).  Returns the
    :class:`ServingEngine`; its ``backend`` and ``memmgr`` are the parts."""
    from .. import configs
    from ..core.bandits import make_policy
    from ..models import registry
    from .costmodel import RooflineCostModel, TPU_V5E
    from .engine import ServingEngine
    from .kv_cache import HostKVStore
    from .memory_manager import ElasticMemoryManager
    from .scheduler import ContinuousBatchingScheduler

    cfg, dcfg = configs.get_config(arch), configs.get_draft_config(arch)
    if reduced:
        cfg, dcfg = configs.reduced(cfg), configs.reduced(dcfg)
    target, draft = registry.get_model(cfg), registry.get_model(dcfg)
    if target.supports_paged and draft.supports_paged:
        # a reduced pair's budget would be thousands of blocks its CPU
        # workloads never touch (and every interpret-mode kernel call walks)
        nb = num_blocks_for(RooflineCostModel(TPU_V5E), cfg, dcfg,
                            BLOCK_SIZE, max_blocks=256 if reduced else None)
    else:
        nb = -(-max_batch * DENSE_MAX_SEQ // BLOCK_SIZE)
    bm = BlockManager(nb, BLOCK_SIZE, prefix_caching=prefix_caching,
                      host_store=HostKVStore(4 * nb) if kv_offload else None)
    backend = make_real_backend(target, draft, max_batch=max_batch,
                                max_seq=DENSE_MAX_SEQ, seed=seed,
                                block_manager=bm)
    sched = ContinuousBatchingScheduler(bm, max_batch=max_batch,
                                        chunk_tokens=chunk_tokens,
                                        prefill_order=prefill_order)
    memmgr = None
    if offload and isinstance(backend, RealBackend):
        # §6.3: the offloaded draft's weight bytes become blocks of BOTH
        # pools, since grow_pools extends the target and the draft pool
        per_block = backend.tkv.bytes_per_block + backend.dkv.bytes_per_block
        memmgr = ElasticMemoryManager(
            bm, draft_blocks=max(-(-registry.param_bytes(dcfg) // per_block),
                                 1),
            offload_fn=backend.offload_draft,
            reload_fn=backend.reload_draft,
            migrate_fn=backend.migrate_pools,
            grow_fn=backend.grow_pools,
            shrink_fn=backend.shrink_pools)
    return ServingEngine(backend, sched,
                         make_policy(policy, gamma_max, seed=seed), memmgr,
                         gamma_max=gamma_max)


# ---------------------------------------------------------------------------
# Legacy dense slot-cache backend (SSM/hybrid/encdec families + baselines)
# ---------------------------------------------------------------------------


def _gather(cache, idx):
    def g(x):
        if x.ndim == 1:
            return x[idx]
        return x[:, idx]
    return jax.tree.map(g, cache)


def _scatter(cache, compact, idx, n_real):
    def s(x, c):
        if x.ndim == 1:
            return x.at[idx[:n_real]].set(c[:n_real])
        return x.at[:, idx[:n_real]].set(c[:, :n_real])
    return jax.tree.map(s, cache, compact)


class DenseSlotBackend:
    """Slot-based continuous batching over dense caches:

      * caches are allocated once for ``max_batch`` slots x ``max_seq``
        positions;
      * each step gathers the active slots into a compact batch (padded to a
        power-of-two bucket), runs the jitted AR / speculative step, and
        scatters the updated slot caches back;
      * prefill is a one-sequence-at-a-time Python loop.

    This is the seed implementation, superseded by :class:`RealBackend` for
    attention families and retained for O(1)-state families and as the
    dense baseline in tests/benchmarks.
    """

    def __init__(self, target: ModelAPI, draft: ModelAPI, *, max_batch: int = 8,
                 max_seq: int = 256, seed: int = 0, sampling: str = "greedy",
                 temperature: float = 1.0):
        self.target = target
        self.draft = draft
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.sampling = sampling
        self.key = jax.random.PRNGKey(seed)

        k1, k2 = jax.random.split(jax.random.PRNGKey(seed + 1))
        self.tparams = target.init(k1)
        self.dparams = draft.init(k2)
        self.dparams_host: Optional[dict] = None  # offloaded copy

        self.tcache = target.init_cache(max_batch, max_seq)
        self.dcache = draft.init_cache(max_batch, max_seq)
        self.last_token = np.zeros(max_batch, np.int32)
        self.tokens_out: Dict[int, List[int]] = {}
        self.slot_of: Dict[int, int] = {}
        self._free_slots = list(range(max_batch))[::-1]

        self._spec = make_spec_step(target, draft, sampling=sampling,
                                    temperature=temperature)
        self._ar = make_ar_step(target, sampling=sampling,
                                temperature=temperature)
        self._spec_jit = jax.jit(self._spec, static_argnames=("gamma",))
        self._ar_jit = jax.jit(self._ar)
        self._prefill_t = jax.jit(lambda p, b: target.prefill(p, b, max_seq))
        self._prefill_d = jax.jit(lambda p, b: draft.prefill(p, b, max_seq))

    # ------------------------------------------------------------------
    def _next_key(self):
        self.key, k = jax.random.split(self.key)
        return k

    def offload_draft(self) -> None:
        self.dparams_host = jax.tree.map(np.asarray, self.dparams)
        self.dparams = None

    def reload_draft(self) -> None:
        assert self.dparams_host is not None
        self.dparams = jax.tree.map(jnp.asarray, self.dparams_host)

    @property
    def draft_resident(self) -> bool:
        return self.dparams is not None

    # ------------------------------------------------------------------
    def prefill(self, seqs: List[Sequence], *, with_draft: bool) -> float:
        t0 = time.perf_counter()
        for s in seqs:
            slot = self._free_slots.pop()
            self.slot_of[s.req_id] = slot
            s.slot = slot
            toks = np.asarray(s.request.prompt_tokens, np.int32)[None, :]
            batch = {"tokens": jnp.asarray(toks)}
            logits, cache1 = self._prefill_t(self.tparams, batch)
            logits.block_until_ready()
            self.tcache = _scatter(self.tcache, cache1, np.array([slot]), 1)
            nxt = int(np.argmax(np.asarray(logits[0, 0])))
            self.last_token[slot] = nxt
            self.tokens_out[s.req_id] = [nxt]
            s.generated = 0  # first token counted at the first decode commit
            if with_draft and self.draft_resident:
                _, dcache1 = self._prefill_d(self.dparams, batch)
                self.dcache = _scatter(self.dcache, dcache1, np.array([slot]), 1)
                s.delta = 0
            else:
                s.delta = s.request.prompt_len
        return time.perf_counter() - t0

    def draft_catchup(self, seqs: List[Sequence]) -> float:
        """Re-prefill the draft cache for sequences whose draft state lags
        (the physical C_switch cost)."""
        if not self.draft_resident:
            return 0.0
        t0 = time.perf_counter()
        for s in seqs:
            if s.delta <= 0:
                continue
            slot = self.slot_of[s.req_id]
            ctx = (list(s.request.prompt_tokens)
                   + self.tokens_out[s.req_id][:-1])
            batch = {"tokens": jnp.asarray(np.asarray(ctx, np.int32)[None, :])}
            _, dcache1 = self._prefill_d(self.dparams, batch)
            jax.block_until_ready(dcache1)
            self.dcache = _scatter(self.dcache, dcache1, np.array([slot]), 1)
            s.delta = 0
        return time.perf_counter() - t0

    # ------------------------------------------------------------------
    def hybrid_step(self, chunks, decode: List[Sequence], gamma: int,
                    *, with_draft: bool) -> StepOutcome:
        """Chunked prefill needs paged caches (RealBackend); the dense slot
        tier still runs monolithic prefill only."""
        if chunks:
            raise NotImplementedError(
                "chunked prefill needs the paged-KV RealBackend — the dense "
                "slot backend prefills monolithically (chunk_tokens=0)")
        return self.step(decode, gamma)

    def step(self, seqs: List[Sequence], gamma: int) -> StepOutcome:
        n = len(seqs)
        bucket = min(_bucket(n), self.max_batch)
        slots = np.array([self.slot_of[s.req_id] for s in seqs], np.int32)
        idx = np.concatenate([slots, np.zeros(bucket - n, np.int32)])

        tc = _gather(self.tcache, idx)
        last = jnp.asarray(self.last_token[idx])

        t0 = time.perf_counter()
        if gamma == 0:
            nxt, tc_new = self._ar_jit(self._next_key(), self.tparams, tc, last)
            jax.block_until_ready(nxt)
            latency = time.perf_counter() - t0
            self.tcache = _scatter(self.tcache, tc_new, idx, n)
            nxt_np = np.asarray(nxt)
            n_committed = []
            for i, s in enumerate(seqs):
                self.tokens_out[s.req_id].append(int(nxt_np[i]))
                self.last_token[slots[i]] = int(nxt_np[i])
                n_committed.append(1)
            return StepOutcome(n_committed=n_committed, latency=latency)

        dc = _gather(self.dcache, idx)
        res = self._spec_jit(self._next_key(), self.tparams, self.dparams,
                             tc, dc, last, gamma=gamma)
        jax.block_until_ready(res.n_accepted)
        latency = time.perf_counter() - t0
        self.tcache = _scatter(self.tcache, res.tcache, idx, n)
        self.dcache = _scatter(self.dcache, res.dcache, idx, n)
        toks = np.asarray(res.tokens)
        n_acc = np.asarray(res.n_accepted)
        last_np = np.asarray(res.last_token)
        n_committed = []
        for i, s in enumerate(seqs):
            committed = [int(t) for t in toks[i] if t >= 0]
            self.tokens_out[s.req_id].extend(committed)
            self.last_token[slots[i]] = int(last_np[i])
            n_committed.append(int(n_acc[i]) + 1)
        return StepOutcome(n_committed=n_committed, latency=latency)

    # ------------------------------------------------------------------
    def release(self, seq: Sequence) -> None:
        slot = self.slot_of.pop(seq.req_id, None)
        if slot is not None:
            self._free_slots.append(slot)

    def output_tokens(self, req_id: int) -> List[int]:
        return self.tokens_out.get(req_id, [])
