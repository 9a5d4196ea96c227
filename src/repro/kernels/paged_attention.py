"""Paged multi-query attention — the TPU analogue of vLLM's PagedAttention.

The kernel reads the stacked KV pool ``(L, NB+1, block_size, KH * hd)``
where it lies in HBM: it takes the pool whole, with the layer index as a
scalar, and copies ``pool[layer, block]`` into VMEM itself.  A block is
one lane-dense ``(block_size, KH * hd)`` tile holding every KV head of
its tokens; each head is a lane slice of it.

One grid step per (sequence, query-row tile).  Inside a step the kernel
walks the sequence's valid pages only — up to the last key any query of
the tile attends, ``lengths - T + t_last + 1`` — in chunks of several
pages, double-buffered: while one chunk is attended the next one's pages
stream in, and the last chunk of a step fetches the next step's first.
Lengths are runtime values (scalar prefetch), so one program serves every
length.  A running (max, sum) softmax accumulates over the chunks in f32;
q, k and v enter the MXU at their stored precision.

The query carries T positions per sequence, so ONE kernel serves all three
real-backend shapes: plain decode (T=1), speculative verification
(T=gamma+1), and prefill / chunked-prefill appends (T up to the prompt
length, split over several row tiles).  ``lengths`` counts the valid tokens
INCLUDING the T new positions; query t attends to page positions
<= lengths - T + t, i.e. causally within the extension.  As for the
oracle, slots past a row's last key must hold finite values (they are
masked, not skipped, inside its last page); pages past it are never read.

The pages per chunk and the rows per tile follow from the call's shapes
(bytes per page, T * G query rows, a VMEM budget): :func:`plan`.
:func:`pages_read` counts the pages a call copies.  Validated in interpret
mode against ref.paged_attention_ref over shape/dtype/T/GQA sweeps with
ragged lengths.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import NEG_INF

LANES = 128
# K bytes one chunk of pages should carry (V carries as many)
CHUNK_BYTES = 128 << 10
# VMEM for the tiles that grow with the query rows (q, out, softmax state)
ROW_VMEM_BYTES = 8 << 20


def plan(n_rows: int, width: int, block_size: int, kv_heads: int,
         head_dim: int, itemsize: int):
    """(pages per chunk, query rows per tile) for one call's shapes.

    A chunk carries about ``CHUNK_BYTES`` of K and at least a lane tile of
    keys, and no more pages than the table holds.  A tile holds every
    query row (T * G) when they fit ``ROW_VMEM_BYTES``; otherwise the
    largest power of two that does."""
    token_bytes = kv_heads * head_dim * itemsize
    tokens = max(LANES, CHUNK_BYTES // token_bytes)
    chunk = max(1, min(width, tokens // block_size))
    per_row = (kv_heads * (head_dim * (4 * itemsize + 4) + 2 * LANES * 4)
               + 3 * chunk * block_size * 4)
    cap = max(16, 1 << ((ROW_VMEM_BYTES // per_row).bit_length() - 1))
    return chunk, (n_rows if n_rows <= cap else cap)


def pages_read(lengths, n_queries: int, heads: int, head_dim: int, pool,
               width: int) -> int:
    """Pages of K (and as many of V) that one call copies, for ``lengths``
    as the call gets them, ``heads`` query heads of ``head_dim``, the
    stacked ``pool`` (or its shape and dtype) and tables ``width`` pages
    wide: per sequence and query-row tile, the pages up to the tile's last
    attended key."""
    _, _, bs, lanes = pool.shape
    kv_heads = lanes // head_dim
    group = heads // kv_heads
    n_rows = n_queries * group
    _, rows = plan(n_rows, width, bs, kv_heads, head_dim,
                   np.dtype(pool.dtype).itemsize)
    n_tiles = -(-n_rows // rows)
    t_last = np.minimum(n_queries - 1,
                        ((np.arange(n_tiles) + 1) * rows - 1) // group)
    end = np.asarray(lengths, np.int64)[:, None] - n_queries + t_last + 1
    return int(np.clip(-(-end // bs), 0, width).sum())


def _kernel(layer_ref, tables_ref, lengths_ref, q_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, sems, slot_ref, m_ref, l_ref, acc_ref, *,
            block_size, chunk, width, n_queries, group, rows, n_tiles,
            kv_heads, head_dim):
    b, tile = pl.program_id(0), pl.program_id(1)
    n_seqs = pl.num_programs(0)
    layer = layer_ref[0]
    pools = ((k_hbm.at[layer], kbuf, 0), (v_hbm.at[layer], vbuf, 1))
    span = chunk * block_size

    def extent(b, tile):
        # pages up to the last key the tile's last query attends
        t_last = lax.min(n_queries - 1, ((tile + 1) * rows - 1) // group)
        end = lengths_ref[b] - n_queries + t_last + 1
        return lax.clamp(0, (end + block_size - 1) // block_size, width)

    def each_page(b, n_pages, c, slot, method):
        # start (or wait for) the copies of chunk c's pages below n_pages:
        # a loop, not unrolled, so that tracing stays cheap at any chunk
        row = b * width + c * chunk
        ends = [(src, buf.at[slot], sems.at[kv, slot])
                for src, buf, kv in pools]

        def page(j, carry):
            blk = tables_ref[row + j]
            for src, dst, sem in ends:
                getattr(pltpu.make_async_copy(src.at[blk], dst.at[j], sem),
                        method)()
            return carry

        lax.fori_loop(0, lax.clamp(0, n_pages - c * chunk, chunk), page, 0)

    n_pages = extent(b, tile)
    n_chunks = lax.max((n_pages + chunk - 1) // chunk, 1)
    # the step after this one, whose first chunk this one's last fetches
    same = tile + 1 < n_tiles
    nxt_b = lax.select(same, b, b + 1)
    nxt_tile = lax.select(same, tile + 1, 0)
    nxt_pages = extent(lax.min(nxt_b, n_seqs - 1), nxt_tile)
    has_next = nxt_b < n_seqs

    @pl.when((b == 0) & (tile == 0))
    def _first():
        # a page the last chunk does not copy keeps what its buffer held:
        # zeros, or a page copied earlier in this call
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)
        slot_ref[0] = 0
        each_page(b, n_pages, 0, 0, "start")

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    slot0 = slot_ref[0]
    # row r of the tile is query t = (tile * rows + r) // group: it may
    # attend to every position at or before its own (length - T + t)
    r = tile * rows + lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    limit = lengths_ref[b] - n_queries + r // group            # (rows, 1)
    scale = head_dim ** -0.5

    def body(c, carry):
        cur = (slot0 + c) % 2
        nxt = 1 - cur
        more = c + 1 < n_chunks

        @pl.when(more | has_next)
        def _prefetch():
            each_page(lax.select(more, b, nxt_b),
                      lax.select(more, n_pages, nxt_pages),
                      lax.select(more, c + 1, 0), nxt, "start")

        slot_ref[0] = nxt
        each_page(b, n_pages, c, cur, "wait")

        pos = c * span + lax.broadcasted_iota(jnp.int32, (1, span), 1)
        visible = pos <= limit                                  # (rows, span)
        kc, vc = kbuf.at[cur], vbuf.at[cur]
        for h in range(kv_heads):
            lanes = pl.ds(h * head_dim, head_dim)
            k = kc[:, :, lanes].reshape(span, head_dim)
            v = vc[:, :, lanes].reshape(span, head_dim)
            s = lax.dot_general(
                q_ref[0, h], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(visible, s, NEG_INF)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[h] = alpha * acc_ref[h] + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_ref[h] = m_new
        return carry

    lax.fori_loop(0, n_chunks, body, 0)
    for h in range(kv_heads):
        o_ref[0, h] = (acc_ref[h] / jnp.maximum(l_ref[h], 1e-30)
                       ).astype(o_ref.dtype)


def paged_attention_pool(q, k_pool, v_pool, layer, block_tables, lengths, *,
                         interpret=False):
    """Attention of a T-token extension over layer ``layer`` of the stacked
    paged pools.

    q: (B, H, D) or (B, T, H, D); k/v_pool: (L, NB+1, block_size, KH * D),
    read in place; layer: int32 scalar; block_tables: (B, max_blocks);
    lengths: (B,) valid tokens including the T new positions -> output of
    the same rank as q."""
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    B, T, H, D = q.shape
    _, _, bs, lanes = k_pool.shape
    KH = lanes // D
    G = H // KH
    width = block_tables.shape[1]
    n_rows = T * G
    chunk, rows = plan(n_rows, width, bs, KH, D, k_pool.dtype.itemsize)
    n_tiles = -(-n_rows // rows)

    # query rows ordered (t, g): row = t * G + g; pad the rows to a whole
    # number of tiles when they span more than one
    qg = q.reshape(B, T, KH, G, D).transpose(0, 2, 1, 3, 4).reshape(
        B, KH, n_rows, D)
    if n_tiles * rows != n_rows:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, n_tiles * rows - n_rows),
                          (0, 0)))

    kernel = functools.partial(
        _kernel, block_size=bs, chunk=chunk, width=width, n_queries=T,
        group=G, rows=rows, n_tiles=n_tiles, kv_heads=KH, head_dim=D)
    tile_spec = pl.BlockSpec((1, KH, rows, D), lambda b, i, *_: (b, 0, i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # layer, block tables, lengths
        grid=(B, n_tiles),
        in_specs=[tile_spec, pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=tile_spec,
        scratch_shapes=[
            pltpu.VMEM((2, chunk, bs, lanes), k_pool.dtype),
            pltpu.VMEM((2, chunk, bs, lanes), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),      # (k / v, buffer)
            pltpu.SMEM((1,), jnp.int32),          # buffer of a step's chunk 0
            pltpu.VMEM((KH, rows, 1), jnp.float32),   # running max
            pltpu.VMEM((KH, rows, 1), jnp.float32),   # running denominator
            pltpu.VMEM((KH, rows, D), jnp.float32),   # output accumulator
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KH, n_tiles * rows, D), q.dtype),
        # steps run in order: each prefetches the next one's first chunk
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="paged_attention",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      block_tables.reshape(-1).astype(jnp.int32), lengths.astype(jnp.int32),
      qg, k_pool, v_pool)
    out = out[:, :, :n_rows].reshape(B, KH, T, G, D).transpose(0, 2, 1, 3, 4)
    out = out.reshape(B, T, H, D)
    return out[:, 0] if squeeze else out


def paged_attention(q, k_pages, v_pages, block_tables, lengths, *,
                    interpret: bool = False):
    """q: (B, H, D) or (B, T, H, D); k/v_pages: (num_blocks, block_size,
    KH, D); block_tables: (B, max_blocks); lengths: (B,) valid tokens
    including the T new positions -> output of the same rank as q.  The
    pages are viewed as a one-layer pool (a reshape, no copy)."""
    nb, bs, KH, D = k_pages.shape
    pool = lambda p: p.reshape(1, nb, bs, KH * D)
    return paged_attention_pool(q, pool(k_pages), pool(v_pages), 0,
                                block_tables, lengths, interpret=interpret)
