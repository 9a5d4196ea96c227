"""Paged-KV runtime state for the real-execution backend.

One :class:`PagedKVRuntime` per model (target / draft) owns the physical
``(L, num_blocks + 1, block_size, KH * hd)`` key/value page arrays (the last
block is the write-off "trash" block absorbing padded-slot writes) and the
host-side per-sequence materialised lengths.  The logical layout — which
sequence owns which blocks — lives in the existing :class:`BlockManager`;
this class only turns those tables into padded int32 device operands.

The zero-copy contract: admission, decode, speculative verification,
chunked prefill, eviction and completion never touch the page tensors from
the host.  Per step, the only host->device traffic is the (B, width) block
tables, (B,) lengths and the token ids; the only device->host traffic is
the sampled tokens (and acceptance counts).  The pages themselves are
donated through the jitted step functions so XLA updates them in place.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence as Seq, Tuple

import jax.numpy as jnp
import numpy as np

from ..kernels import ops
from ..models.registry import ModelAPI
from .costmodel import kv_bytes_per_token
from .kv_cache import BlockManager, MigrationPlan
from .request import Sequence


def bucket_size(n: int) -> int:
    """Next power of two (jit-cache-friendly padding)."""
    return 1 << max(n - 1, 0).bit_length()


def num_blocks_for(cost_model, target_cfg, draft_cfg, block_size: int, *,
                   min_blocks: int = 64, max_blocks: Optional[int] = None,
                   reserve_frac: float = 0.1) -> int:
    """Size the physical pool from the roofline HBM budget: the bytes left
    beside both models' weights, over what one block costs in BOTH pools
    (every block id holds a target page and a draft page) plus its share of
    the elastic cycle's resize copy.  That copy is the peak of the cycle:
    :meth:`PagedKVRuntime.shrink` slices one base-size page array (k or v)
    of the larger pool while the grown pool, which by then holds the
    offloaded draft's bytes as blocks, is still live.  The reserve covers
    the step programs' temporaries.  At least ``min_blocks``; at most
    ``max_blocks`` when given."""
    hw = cost_model.hw
    avail = (hw.hbm_bytes * hw.chips * (1 - reserve_frac)
             - cost_model.weight_bytes(target_cfg)
             - cost_model.weight_bytes(draft_cfg))
    kv_t = kv_bytes_per_token(target_cfg, cost_model.dtype_bytes)
    kv_d = kv_bytes_per_token(draft_cfg, cost_model.dtype_bytes)
    per_block = block_size * (kv_t + kv_d + max(kv_t, kv_d) // 2)
    nb = max(int(avail // per_block), min_blocks)
    return nb if max_blocks is None else min(nb, max_blocks)


class PagedKVRuntime:
    """Physical paged KV pool + host length bookkeeping for one model."""

    def __init__(self, api: ModelAPI, bm: BlockManager):
        if not api.supports_paged:
            raise NotImplementedError(
                f"family {api.cfg.family!r} has no paged-KV path")
        self.api = api
        self.bm = bm
        self.num_blocks = bm.total_blocks
        self.block_size = bm.block_size
        self.trash = self.num_blocks          # id of the write-off block
        self.pages = api.init_paged_cache(self.num_blocks, self.block_size)
        self.ctx: Dict[int, int] = {}         # req_id -> materialised tokens

    @property
    def bytes_per_block(self) -> int:
        k = self.pages["k_pages"]
        L, _, bs, row = k.shape
        return 2 * L * bs * row * k.dtype.itemsize  # k + v

    def transfer_bytes(self, n_blocks: int) -> int:
        """Wire bytes for migrating ``n_blocks`` of this pool between
        replicas (the disaggregated handoff path — what the sim tier prices
        at interconnect bandwidth)."""
        return n_blocks * self.bytes_per_block

    # ------------------------------------------------------------------
    # copy-on-write + elastic physical pool (§6.3/6.4 on the real tier)
    # ------------------------------------------------------------------
    def apply_copies(self, src: Seq[int], dst: Seq[int]) -> None:
        """Execute block copies src[i] -> dst[i] on-device in ONE batched
        block-migration launch per page array (the CoW fork path and the
        §6.4 step-3 data movement share the same kernel).  No host
        round-trip: the pages stay on-device and are updated in place, only
        the int32 index vectors travel."""
        if not len(src):
            return
        for key in ("k_pages", "v_pages"):
            self.pages[key] = ops.migrate_blocks(self.pages[key], src, dst)

    def apply_plan(self, plan: MigrationPlan) -> None:
        """§6.4 step 3 on the physical paged pools."""
        self.apply_copies(plan.src, plan.dst)

    def spill_blocks(self, ids: Seq[int]) -> Dict[str, np.ndarray]:
        """Device→host gather of whole blocks for the host KV offload tier:
        ONE batched index gather per page array (k/v), materialised to host
        numpy.  Returned arrays have shape (L, n, block_size, KH * hd) with
        the block axis second, so ``arr[:, i]`` is block ``ids[i]``'s
        payload for a single :class:`~.kv_cache.HostBlockRecord`."""
        idx = jnp.asarray(list(ids), jnp.int32)
        return {key: np.asarray(arr[:, idx])
                for key, arr in self.pages.items()}

    def restore_blocks(self, ids: Seq[int],
                       payloads: Dict[str, np.ndarray]) -> None:
        """Host→device scatter of spilled block payloads back into the page
        arrays — one batched index-vector scatter per pool, the same data
        movement shape as the block-migration path with the source staged
        from host memory.  ``payloads`` mirrors :meth:`spill_blocks`'s
        (L, n, block_size, KH * hd) layout."""
        if not len(ids):
            return
        idx = jnp.asarray(list(ids), jnp.int32)
        for key in self.pages:
            self.pages[key] = self.pages[key].at[:, idx].set(
                jnp.asarray(payloads[key], self.pages[key].dtype))

    def grow(self, extra_blocks: int) -> None:
        """§6.3 expansion of the physical pool: extend both page arrays by
        ``extra_blocks``, keeping the trash block LAST.  The old trash slot
        is recycled as ordinary storage — its garbage content is never read
        because per-sequence lengths gate every attention read, and every
        block is written before its positions become readable.  One array
        grows at a time and is waited for before the next: dispatch is
        asynchronous, so without the wait every old array would still be
        live beside every new one."""
        for key in list(self.pages):
            x = self.pages.pop(key)
            pad = [(0, 0)] * x.ndim
            pad[1] = (0, extra_blocks)
            self.pages[key] = jnp.pad(x, pad).block_until_ready()
        self.num_blocks += extra_blocks
        self.trash = self.num_blocks

    def shrink(self, to_blocks: int) -> None:
        """§6.4 step 5 on the physical pool: trim to ``to_blocks`` + trash.
        Must run after the BlockManager committed its contraction (no table
        references an id >= to_blocks).  The surviving slot at index
        ``to_blocks`` becomes the new trash block.  Like :meth:`grow`, one
        array is trimmed and waited for at a time."""
        assert to_blocks <= self.num_blocks, (to_blocks, self.num_blocks)
        for key in list(self.pages):
            self.pages[key] = self.pages.pop(key)[
                :, :to_blocks + 1].block_until_ready()
        self.num_blocks = to_blocks
        self.trash = to_blocks

    def batch_tables(self, seqs: Seq[Sequence], batch: int
                     ) -> Tuple[np.ndarray, np.ndarray]:
        """Padded (batch, width) int32 block tables + (batch,) materialised
        lengths for one step.  Rows beyond ``len(seqs)`` and table entries
        beyond a sequence's allocation are the trash id, which both satisfies
        the kernel's "any valid id" padding contract and guarantees padded
        slots can only ever write to the trash block."""
        # the physical pool must follow BlockManager.expand()/contraction in
        # lockstep (``grow``/``shrink``, wired through the memory manager's
        # grow_fn/shrink_fn hooks) — a drifted allocator would hand out ids
        # colliding with the trash block / falling outside the pages, so
        # fail loudly instead of corrupting KV
        assert self.bm.total_blocks == self.num_blocks, (
            "BlockManager pool size drifted from the physical paged pool "
            f"({self.bm.total_blocks} != {self.num_blocks}); wire "
            "PagedKVRuntime.grow/shrink into the ElasticMemoryManager "
            "(see RealBackend.grow_pools/shrink_pools)")
        rows: List[List[int]] = [list(self.bm.tables.get(s.req_id, ()))
                                 for s in seqs]
        width = bucket_size(max((len(r) for r in rows), default=1) or 1)
        tables = np.full((batch, width), self.trash, np.int32)
        lengths = np.zeros((batch,), np.int32)
        for i, (s, row) in enumerate(zip(seqs, rows)):
            tables[i, :len(row)] = row
            lengths[i] = self.ctx.get(s.req_id, 0)
        return tables, lengths
