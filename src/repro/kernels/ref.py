"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth the kernels are validated against
(``tests/test_kernels.py`` sweeps shapes/dtypes with assert_allclose).

The paged-attention oracle takes one layer's pages ``(num_blocks,
block_size, KH, D)`` (``ops`` slices it out of the stacked pool), gathers
every page of the padded table and masks the positions past each query's
own: the same answer as the kernel, which reads the pool in place and only
the pages up to each row's last attended key.  Slots it masks must hold
finite values.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def migrate_blocks_ref(pool, src, dst):
    """pool: (L, num_blocks, ...); copy blocks src -> dst along axis 1
    (one-to-one)."""
    return pool.at[:, dst].set(pool[:, src])


def paged_attention_ref(q, k_pages, v_pages, block_tables, lengths):
    """Attention of a T-token extension over paged KV.

    q:            (B, H, D) single-query decode, or (B, T, H, D) multi-query —
                  one kernel shape serves plain decode (T=1), speculative
                  verification (T=gamma+1) and chunked-prefill appends
                  (T=chunk tokens just scattered into freshly grown blocks)
    k/v_pages:    (num_blocks, block_size, KH, D)
    block_tables: (B, max_blocks) int32 (padded with any valid id)
    lengths:      (B,) valid token counts INCLUDING the T new positions
                  (whose K/V are already written into the pages); query t
                  attends to positions <= lengths - T + t, i.e. causally
                  within the extension
    returns       same rank as q
    """
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    B, T, H, D = q.shape
    nb, bs, KH, _ = k_pages.shape
    G = H // KH
    max_blocks = block_tables.shape[1]
    S = max_blocks * bs

    # gather each sequence's KV contiguously
    k = k_pages[block_tables].reshape(B, S, KH, D)
    v = v_pages[block_tables].reshape(B, S, KH, D)
    qg = q.reshape(B, T, KH, G, D).astype(jnp.float32) * (D ** -0.5)
    s = jnp.einsum("btkgd,bskd->bkgts", qg, k.astype(jnp.float32))
    limit = lengths[:, None] - T + jnp.arange(T)[None, :]         # (B, T)
    mask = jnp.arange(S)[None, None, :] <= limit[:, :, None]      # (B, T, S)
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgts,bskd->btkgd", p, v.astype(jnp.float32))
    out = out.reshape(B, T, H, D).astype(q.dtype)
    return out[:, 0] if squeeze else out


def flash_attention_ref(q, k, v, *, causal=True):
    """Standard full attention. q: (B, S, H, D), k/v: (B, S, KH, D)."""
    B, S, H, D = q.shape
    KH = k.shape[2]
    G = H // KH
    qg = q.reshape(B, S, KH, G, D).astype(jnp.float32) * (D ** -0.5)
    s = jnp.einsum("bskgd,btkd->bkgst", qg, k.astype(jnp.float32))
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", p, v.astype(jnp.float32))
    return out.reshape(B, S, H, D).astype(q.dtype)
