"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.block_migration import migrate_blocks
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import (paged_attention,
                                           paged_attention_pool, pages_read,
                                           plan)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("nb,row_shape,m", [
    (8, (4, 2, 4), 4), (16, (2, 8, 16), 8), (5, (3, 2, 2), 2),
    # the paged pools' (L, NB+1, block_size, KH * hd) layout: draft and
    # target rows of the 7b pair; m=3 pads the map to 4 entries
    (9, (16, 128), 3), (12, (16, 512), 5)])
def test_block_migration_sweep(dtype, nb, row_shape, m):
    key = jax.random.PRNGKey(0)
    L = 3
    x = jax.random.normal(key, (L, nb) + row_shape).astype(dtype)
    src = np.random.default_rng(1).choice(nb, m, replace=False)
    dst = np.asarray([i for i in range(nb) if i not in src][:m])
    want = np.asarray(ref.migrate_blocks_ref(x, src, dst))
    np.testing.assert_array_equal(want[:, dst], np.asarray(x[:, src]))
    got = migrate_blocks(x, jnp.asarray(src), jnp.asarray(dst),
                         interpret=True)
    np.testing.assert_array_equal(np.asarray(got), want)
    with ops.interpret_kernels():     # dispatch + power-of-two padding
        got = ops.migrate_blocks(x, src, dst)   # donates x
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,KH,D,bs,maxb", [
    (2, 4, 4, 64, 16, 3),    # MHA
    (3, 8, 2, 64, 16, 4),    # GQA
    (2, 8, 1, 128, 8, 5),    # MQA
])
def test_paged_attention_sweep(B, H, KH, D, bs, maxb, dtype):
    key = jax.random.PRNGKey(2)
    nblocks = maxb * B + 2
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (B, H, D)).astype(dtype)
    kp = jax.random.normal(ks[1], (nblocks, bs, KH, D)).astype(dtype)
    vp = jax.random.normal(ks[2], (nblocks, bs, KH, D)).astype(dtype)
    tables = jax.random.randint(ks[3], (B, maxb), 0, nblocks)
    lengths = jnp.asarray([1 + (7 * i) % (maxb * bs) for i in range(B)])
    want = ref.paged_attention_ref(q, kp, vp, tables, lengths)
    got = paged_attention(q, kp, vp, tables, lengths, interpret=True)
    atol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)


# T=160 at G=8 spans two query-row tiles (the last one padded; see plan)
@pytest.mark.parametrize("T", [1, 4, 8, 160])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,KH,D,bs,maxb", [
    (2, 4, 4, 64, 16, 3),    # MHA
    (3, 8, 2, 64, 16, 4),    # GQA
    (2, 8, 1, 128, 8, 5),    # MQA
])
def test_paged_attention_multiquery_sweep(B, H, KH, D, bs, maxb, dtype, T):
    """Multi-query extension (T=1 decode / T=gamma+1 verify / T=chunk
    append or prefill) vs the jnp oracle, over GQA ratios and ragged
    lengths."""
    key = jax.random.PRNGKey(7)
    maxb = max(maxb, -(-(T + 40) // bs))     # room for T plus a context
    nblocks = maxb * B + 2
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (B, T, H, D)).astype(dtype)
    kp = jax.random.normal(ks[1], (nblocks, bs, KH, D)).astype(dtype)
    vp = jax.random.normal(ks[2], (nblocks, bs, KH, D)).astype(dtype)
    tables = jax.random.randint(ks[3], (B, maxb), 0, nblocks)
    # ragged: every sequence's total length (incl. the T new tokens) differs
    lengths = jnp.asarray([T + (7 * i) % (maxb * bs - T + 1)
                           for i in range(B)])
    want = ref.paged_attention_ref(q, kp, vp, tables, lengths)
    got = paged_attention(q, kp, vp, tables, lengths, interpret=True)
    atol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)


def test_paged_attention_multiquery_is_causal_within_extension():
    """Query t must not see the K/V of queries t' > t: the T-token oracle
    output at row t equals a fresh single-query call at length - T + t + 1."""
    B, T, H, KH, D, bs, maxb = 2, 4, 4, 2, 32, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(9), 4)
    nblocks = maxb * B + 1
    q = jax.random.normal(ks[0], (B, T, H, D))
    kp = jax.random.normal(ks[1], (nblocks, bs, KH, D))
    vp = jax.random.normal(ks[2], (nblocks, bs, KH, D))
    tables = jax.random.randint(ks[3], (B, maxb), 0, nblocks)
    lengths = jnp.asarray([maxb * bs, maxb * bs - 5])
    multi = ref.paged_attention_ref(q, kp, vp, tables, lengths)
    for t in range(T):
        single = ref.paged_attention_ref(q[:, t], kp, vp, tables,
                                         lengths - T + t + 1)
        np.testing.assert_allclose(np.asarray(multi[:, t]),
                                   np.asarray(single), atol=2e-5)


# the paper pair's attention: target 28/4 heads of 128, draft 14/2 of 64
PAPER_HEADS = {"target": (28, 4, 128), "draft": (14, 2, 64)}
BS, WIDTH = 16, 40


def _one_tile_plus_one(H, KH, D):
    """The least T whose T * G query rows span two row tiles."""
    _, rows = plan(1 << 20, WIDTH, BS, KH, D, 2)
    return rows // (H // KH) + 1


def _ragged_lengths(T, chunk):
    """Valid keys per row, T new ones included: one key, exact page
    boundaries, a page past a whole chunk, the full table, and the rest
    ragged far below it."""
    keys = [1, BS, 3 * BS, chunk * BS + 1, WIDTH * BS, 17, 250, 5 * BS - 1]
    return np.asarray([min(max(k, T), WIDTH * BS) for k in keys], np.int32)


def _paper_case(which, T, L=1, seed=0):
    """q, stacked bf16 pools (L, NB+1, BS, KH * D), disjoint block tables
    (the trash block last) and ragged lengths at the paper pair's widths."""
    H, KH, D = PAPER_HEADS[which]
    chunk, _ = plan(T * (H // KH), WIDTH, BS, KH, D, 2)
    lengths = _ragged_lengths(T, chunk)
    B = len(lengths)
    nb = B * WIDTH + 1
    rng = np.random.default_rng(seed)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, T, H, D)).astype(jnp.bfloat16)
    kpool = jax.random.normal(ks[1], (L, nb, BS, KH * D)).astype(jnp.bfloat16)
    vpool = jax.random.normal(ks[2], (L, nb, BS, KH * D)).astype(jnp.bfloat16)
    tables = rng.permutation(nb - 1).reshape(B, WIDTH).astype(np.int32)
    return q, kpool, vpool, tables, lengths


def _oracle(q, kpool, vpool, layer, tables, lengths):
    D = q.shape[-1]
    _, nb, bs, lanes = kpool.shape
    heads = lambda p: p[layer].reshape(nb, bs, lanes // D, D)
    return ref.paged_attention_ref(q, heads(kpool), heads(vpool),
                                   jnp.asarray(tables), jnp.asarray(lengths))


def _close(got, want):
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)


@pytest.mark.parametrize("which", ["target", "draft"])
@pytest.mark.parametrize("T", [1, 6, "two_tiles"])
def test_paged_attention_paper_widths_ragged(which, T):
    """G=7 at the pair's head widths, rows from one key to the full table
    (page counts on and off the kernel's chunk), T=1 decode, T=6 verify
    and an extension one query past a single row tile."""
    if T == "two_tiles":
        T = _one_tile_plus_one(*PAPER_HEADS[which])
    q, kpool, vpool, tables, lengths = _paper_case(which, T)
    got = paged_attention_pool(q, kpool, vpool, 0, tables, lengths,
                               interpret=True)
    _close(got, _oracle(q, kpool, vpool, 0, tables, lengths))


@pytest.mark.parametrize("layer", [1, 2])
@pytest.mark.parametrize("which", ["target", "draft"])
def test_paged_attention_reads_its_layer_of_the_stacked_pool(which, layer):
    q, kpool, vpool, tables, lengths = _paper_case(which, 6, L=3, seed=layer)
    got = paged_attention_pool(q, kpool, vpool, jnp.int32(layer), tables,
                               lengths, interpret=True)
    _close(got, _oracle(q, kpool, vpool, layer, tables, lengths))
    other = _oracle(q, kpool, vpool, layer - 1, tables, lengths)
    assert not np.allclose(np.asarray(got, np.float32),
                           np.asarray(other, np.float32), atol=2e-2)


@pytest.mark.parametrize("which", ["target", "draft"])
@pytest.mark.parametrize("T", [1, 6])
def test_paged_attention_never_reads_past_a_row(which, T):
    """Every page past each row's last key in its table, and every block no
    table names, holds NaN: the kernel's output equals the oracle's on the
    clean pools, so it never read them."""
    q, kpool, vpool, tables, lengths = _paper_case(which, T, L=2, seed=3)
    want = _oracle(q, kpool, vpool, 1, tables, lengths)
    read = np.zeros(kpool.shape[1], bool)
    for row, n in zip(tables, lengths):
        read[row[:-(-int(n) // BS)]] = True
    mask = jnp.asarray(~read)[None, :, None, None]
    nan = jnp.asarray(np.nan, kpool.dtype)
    got = paged_attention_pool(q, jnp.where(mask, nan, kpool),
                               jnp.where(mask, nan, vpool), 1, tables,
                               lengths, interpret=True)
    assert np.isfinite(np.asarray(got, np.float32)).all()
    _close(got, want)


def test_pages_read_counts_each_rows_valid_pages():
    """Decode and verify copy each row's pages up to its last key, padded
    rows (length T) included; an extension over two row tiles copies the
    first tile's pages up to its own last query, then the row's."""
    pool = jax.ShapeDtypeStruct((2, 9, BS, 4 * 128), jnp.bfloat16)
    lengths = np.asarray([1, BS, BS + 1, WIDTH * BS, 1])
    assert pages_read(lengths, 1, 28, 128, pool, WIDTH) == 1 + 1 + 2 + 40 + 1
    assert pages_read(lengths + 5, 6, 28, 128, pool, WIDTH) == (
        1 + 2 + 2 + 40 + 1)
    T = _one_tile_plus_one(28, 4, 128)
    _, rows = plan(T * 7, WIDTH, BS, 4, 128, 2)
    first = (rows - 1) // 7 + 1           # keys the first tile attends
    n = 300 + T
    assert pages_read(np.asarray([n]), T, 28, 128, pool, WIDTH) == (
        -(-(n - T + first) // BS) + -(-n // BS))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("B,S,H,KH,D", [(2, 256, 4, 4, 64),
                                        (1, 128, 8, 2, 128),
                                        (2, 384, 4, 1, 64)])
def test_flash_attention_sweep(B, S, H, KH, D, causal, dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, S, H, D)).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, KH, D)).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, KH, D)).astype(dtype)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128,
                          interpret=True)
    atol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)


def test_flash_matches_model_blockwise():
    """The model's chunked attention, the kernel, and the naive ref agree."""
    from repro.models.common import blockwise_attention
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    B, S, H, KH, D = 2, 256, 4, 2, 64
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, KH, D))
    v = jax.random.normal(ks[2], (B, S, KH, D))
    a = ref.flash_attention_ref(q, k, v, causal=True)
    b = blockwise_attention(q, k, v, causal=True, chunk=64)
    c = blockwise_attention(q, k, v, causal=True, chunk=64, unroll=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
    np.testing.assert_allclose(np.asarray(b), np.asarray(c), atol=1e-6)
