"""Paged multi-query attention — the TPU analogue of vLLM's PagedAttention.

One grid cell per (sequence, kv-head, query-row tile, page); the
scalar-prefetched block table drives the BlockSpec index map so each
sequence's non-contiguous KV blocks stream through VMEM.  A running
(max, sum) softmax accumulates across the sequence's pages.  The VMEM
working set is one (block_size, head_dim) page pair plus one
(rows, head_dim) query/accumulator tile of at most ``MAX_ROWS`` rows —
bounded whatever the context length and the extension length T.

The query carries T positions per sequence, so ONE kernel serves all three
real-backend shapes: plain decode (T=1), speculative verification
(T=gamma+1), and prefill / chunked-prefill appends (T up to the prompt
length, split over several row tiles).  ``lengths`` counts the valid tokens
INCLUDING the T new positions; query t attends to page positions
<= lengths - T + t, i.e. causally within the extension.

Validated in interpret mode against ref.paged_attention_ref over
shape/dtype/T/GQA sweeps with ragged lengths.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import NEG_INF

# query rows (T * G, ordered t-major) per grid cell: bounds the VMEM tile
MAX_ROWS = 512


def _paged_attn_kernel(tables_ref, lengths_ref, q_ref, k_ref, v_ref, o_ref,
                       m_ref, l_ref, acc_ref, *, block_size, pages_per_seq,
                       n_queries, group, rows):
    b = pl.program_id(0)
    tile = pl.program_id(2)
    page = pl.program_id(3)

    @pl.when(page == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)           # (rows, D)
    k = k_ref[0, 0].astype(jnp.float32)           # (block_size, D)
    v = v_ref[0, 0].astype(jnp.float32)
    D = q.shape[-1]

    s = (q * (D ** -0.5)) @ k.T                   # (rows, block_size)
    length = lengths_ref[b]
    pos = page * block_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_size), 1)
    # row r of the tile is query t = (tile * rows + r) // group: it may
    # attend to every position at or before its own (length - n_queries + t)
    r = tile * rows + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    limit = length - n_queries + r // group       # (rows, 1)
    s = jnp.where(pos <= limit, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    scale = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * scale + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * scale + p @ v
    m_ref[...] = m_new

    @pl.when(page == pages_per_seq - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                       ).astype(o_ref.dtype)


def paged_attention(q, k_pages, v_pages, block_tables, lengths, *,
                    interpret: bool = False):
    """q: (B, H, D) or (B, T, H, D); k/v_pages: (num_blocks, block_size,
    KH, D); block_tables: (B, max_blocks); lengths: (B,) valid tokens
    including the T new positions -> output of the same rank as q."""
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    B, T, H, D = q.shape
    nb, bs, KH, _ = k_pages.shape
    G = H // KH
    pages_per_seq = block_tables.shape[1]

    # query tile rows ordered (t, g): row = t * G + g; pad the rows to a
    # whole number of tiles when they span more than one
    n_rows = T * G
    rows = n_rows if n_rows <= MAX_ROWS else MAX_ROWS
    n_tiles = -(-n_rows // rows)
    qg = q.reshape(B, T, KH, G, D).transpose(0, 2, 1, 3, 4).reshape(
        B, KH, n_rows, D)
    if n_tiles * rows != n_rows:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, n_tiles * rows - n_rows),
                          (0, 0)))
    # kv pages viewed per head: (num_blocks, KH, block_size, D)
    kp = jnp.swapaxes(k_pages, 1, 2)
    vp = jnp.swapaxes(v_pages, 1, 2)

    grid = (B, KH, n_tiles, pages_per_seq)
    kernel = functools.partial(_paged_attn_kernel, block_size=bs,
                               pages_per_seq=pages_per_seq, n_queries=T,
                               group=G, rows=rows)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block_tables, lengths
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, rows, D),
                         lambda b, h, i, p, t_ref, l_ref: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bs, D),
                         lambda b, h, i, p, t_ref, l_ref:
                         (t_ref[b, p], h, 0, 0)),
            pl.BlockSpec((1, 1, bs, D),
                         lambda b, h, i, p, t_ref, l_ref:
                         (t_ref[b, p], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rows, D),
                               lambda b, h, i, p, t_ref, l_ref: (b, h, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),   # running max
            pltpu.VMEM((rows, 1), jnp.float32),   # running denominator
            pltpu.VMEM((rows, D), jnp.float32),   # output accumulator
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KH, n_tiles * rows, D), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32), qg, kp, vp)
    out = out[:, :, :n_rows].reshape(B, KH, T, G, D).transpose(0, 2, 1, 3, 4)
    out = out.reshape(B, T, H, D)
    return out[:, 0] if squeeze else out
