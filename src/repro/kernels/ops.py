"""Kernel dispatch — the one place that picks how a kernel runs.

On a TPU every op runs its compiled Pallas kernel.  Elsewhere it runs the
pure-jnp oracle (``ref.py``), unless the caller is inside
:func:`interpret_kernels`, which runs the Pallas kernels in interpret mode
(tests and the CPU rehearsal of ``chip_smoke.py``).  The choice is made
when a function is traced, so a jitted step picks it up at its first
call.
"""
from __future__ import annotations

import contextlib
import contextvars
import functools

import jax
import numpy as np

from . import block_migration, flash_attention as flash_mod, ref
from . import paged_attention as paged_mod

_INTERPRET = contextvars.ContextVar("interpret_kernels", default=False)


def kernel_mode() -> str:
    """``"compiled"`` on a TPU; ``"interpret"`` inside
    :func:`interpret_kernels`; ``"oracle"`` otherwise."""
    if jax.default_backend() == "tpu":
        return "compiled"
    return "interpret" if _INTERPRET.get() else "oracle"


@contextlib.contextmanager
def interpret_kernels():
    """Run the Pallas kernels in interpret mode off the TPU.

    The mode is read when a function is traced, and ``jax.jit`` does not
    key its cache on it: a jitted function traced outside this context is
    reused unchanged inside it, and the reverse.  Build the jitted step
    programs (a backend, an engine) inside the context that should run
    them.  ``migrate_blocks`` passes the mode as a static argument and is
    exempt."""
    token = _INTERPRET.set(True)
    try:
        yield
    finally:
        _INTERPRET.reset(token)


def paged_attention(q, k_pool, v_pool, layer, block_tables, lengths):
    """Attention over layer ``layer`` of the stacked paged pools (L, NB+1,
    block_size, KH * D).  q may be (B, H, D) single-query decode or (B, T,
    H, D) multi-query (speculative verify / prefill extensions); see ref
    for masking.  The kernel reads the pools in place; the oracle slices
    the layer."""
    mode = kernel_mode()
    if mode == "oracle":
        D = q.shape[-1]
        _, nb, bs, lanes = k_pool.shape
        heads = lambda p: jax.lax.dynamic_index_in_dim(
            p, layer, keepdims=False).reshape(nb, bs, lanes // D, D)
        return ref.paged_attention_ref(q, heads(k_pool), heads(v_pool),
                                       block_tables, lengths)
    return paged_mod.paged_attention_pool(
        q, k_pool, v_pool, layer, block_tables, lengths,
        interpret=mode == "interpret")


@functools.partial(jax.jit, static_argnames=("mode",), donate_argnums=(0,))
def _migrate(pool, src, dst, *, mode):
    if mode == "oracle":
        return ref.migrate_blocks_ref(pool, src, dst)
    return block_migration.migrate_blocks(pool, src, dst,
                                          interpret=mode == "interpret")


def migrate_blocks(pool, src, dst):
    """Copy blocks ``src[i] -> dst[i]`` along axis 1 of ``pool`` (L,
    num_blocks, ...) in one launch; sources and destinations are disjoint.
    ``pool`` is donated: use the result.  The map is padded to a
    power-of-two length by repeating its last entry (a repeated copy writes
    the same bytes), so a handful of programs serve every migration size."""
    src = np.asarray(src, np.int32)
    dst = np.asarray(dst, np.int32)
    n = len(src)
    if n == 0:
        return pool
    pad = (1 << (n - 1).bit_length()) - n
    src = np.concatenate([src, np.full(pad, src[-1], np.int32)])
    dst = np.concatenate([dst, np.full(pad, dst[-1], np.int32)])
    return _migrate(pool, src, dst, mode=kernel_mode())


def flash_attention(q, k, v, *, causal=True):
    mode = kernel_mode()
    if mode == "oracle":
        return ref.flash_attention_ref(q, k, v, causal=causal)
    return flash_mod.flash_attention(q, k, v, causal=causal,
                                     interpret=mode == "interpret")
