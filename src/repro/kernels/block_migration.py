"""KV-cache block-migration kernel — the paper's §6.4 step-3 Triton kernel,
adapted to TPU with Pallas.

The paper launches one thread block per migrated KV block and moves it with
vectorised load/stores.  On TPU the block never needs to visit VMEM: one
grid step per migration entry issues a single DMA that copies block
``src[i]`` of every layer to block ``dst[i]`` straight from HBM to HBM.
The scalar-prefetched migration map supplies the offsets, the pool keeps
its own layout (no transpose; the paged pools' ``(L, num_blocks,
block_size, KH * hd)`` blocks are lane-dense for every head size), and
``input_output_aliases`` makes the move in place — no second pool
allocation, matching the Triton kernel's in-place compaction semantics.

Sources and destinations must be disjoint (the CoW fork and contraction
plans guarantee it); a repeated entry copies the same bytes again, which
is how ``ops.migrate_blocks`` pads the map to a bucketed length.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _migrate_kernel(src_ref, dst_ref, x_hbm, o_hbm, sem):
    i = pl.program_id(0)
    copy = pltpu.make_async_copy(x_hbm.at[:, pl.ds(src_ref[i], 1)],
                                 o_hbm.at[:, pl.ds(dst_ref[i], 1)], sem)
    copy.start()
    copy.wait()


def migrate_blocks(pool, src, dst, *, interpret: bool = False):
    """pool: (L, num_blocks, ...) — copy blocks ``src[i] -> dst[i]`` along
    axis 1, in place.  ``interpret=True`` runs the Pallas interpreter (CPU
    tests); the jnp oracle is :func:`ref.migrate_blocks_ref`."""
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(src.shape[0],),
        in_specs=[any_space],
        out_specs=any_space,
        scratch_shapes=[pltpu.SemaphoreType.DMA],
    )
    return pl.pallas_call(
        _migrate_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={2: 0},  # pool aliases the output: in-place move
        interpret=interpret,
        name="block_migration",
    )(src.astype(jnp.int32), dst.astype(jnp.int32), pool)
