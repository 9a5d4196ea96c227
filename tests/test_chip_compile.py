"""Compile the real tier's kernels and step programs for a described TPU v5e.

Nothing runs: the TPU compiler, installed beside JAX, compiles for a chip
that is described and not attached, and refuses what the chip would refuse
(tiling, VMEM, HBM).  The topology is described inside a fixture, never at
import, so every test worker collects the same tests and only the worker
given this file loads the TPU library.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.core.spec_decode import make_paged_ar_step, make_paged_spec_step
from repro.kernels import ops
from repro.kernels.block_migration import migrate_blocks
from repro.kernels.paged_attention import (paged_attention,
                                           paged_attention_pool)
from repro.models import registry
from repro.serving.costmodel import RooflineCostModel, TPU_V5E
from repro.serving.paged_runtime import num_blocks_for
from repro.serving.real_backend import BLOCK_SIZE as BLOCK

ARCH = "paper-7b-1chip"
GAMMA_MAX = 5
B, MAX_PROMPT = 8, 2048
# the benchmark cell's decode and verify calls: 64 rows, 128-page tables
CELL_B, CELL_WIDTH = 64, 128
# the pool build_real_engine sizes for the pair on one chip
NUM_BLOCKS = num_blocks_for(RooflineCostModel(TPU_V5E),
                            configs.get_config(ARCH),
                            configs.get_draft_config(ARCH), BLOCK)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip cannot be read back from the
    # persistent cache: keep it out of the cache
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _widths(draft):
    get = configs.get_draft_config if draft else configs.get_config
    c = get(ARCH)
    return c.num_heads, c.num_kv_heads, c.resolved_head_dim, c.num_layers


@pytest.mark.parametrize("T", [1, GAMMA_MAX + 1, MAX_PROMPT])
@pytest.mark.parametrize("draft", [False, True], ids=["target", "draft"])
def test_paged_attention_compiles(one_chip, draft, T):
    H, KH, D, _ = _widths(draft)
    s = lambda *a: _sds(one_chip, *a)
    pages = s((NUM_BLOCKS + 1, BLOCK, KH, D), jnp.bfloat16)
    compiled = jax.jit(paged_attention).lower(
        s((B, T, H, D), jnp.bfloat16), pages, pages,
        s((B, MAX_PROMPT // BLOCK), jnp.int32), s((B,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("T", [1, GAMMA_MAX + 1])
@pytest.mark.parametrize("draft", [False, True], ids=["target", "draft"])
def test_paged_attention_compiles_at_cell_shapes(one_chip, draft, T):
    """The kernel as the serving steps call it: the whole stacked pool of
    every layer, a runtime layer index, the cell's batch and tables."""
    H, KH, D, L = _widths(draft)
    s = lambda *a: _sds(one_chip, *a)
    pool = s((L, NUM_BLOCKS + 1, BLOCK, KH * D), jnp.bfloat16)
    compiled = jax.jit(paged_attention_pool).lower(
        s((CELL_B, T, H, D), jnp.bfloat16), pool, pool, s((), jnp.int32),
        s((CELL_B, CELL_WIDTH), jnp.int32),
        s((CELL_B,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # nothing pool-sized besides the pools themselves
    assert compiled.memory_analysis().temp_size_in_bytes < (
        NUM_BLOCKS * BLOCK * KH * D * 2 // 100)


@pytest.mark.parametrize("draft", [False, True], ids=["target", "draft"])
def test_block_migration_compiles(one_chip, draft):
    _, KH, D, L = _widths(draft)
    s = lambda *a: _sds(one_chip, *a)
    pool = s((L, NUM_BLOCKS + 1, BLOCK, KH * D), jnp.bfloat16)
    compiled = jax.jit(migrate_blocks, donate_argnums=(0,)).lower(
        pool, s((64,), jnp.int32), s((64,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # in place: the pool is the output, nothing pool-sized is allocated
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes == ma.output_size_in_bytes
    assert ma.temp_size_in_bytes < ma.output_size_in_bytes // 100


@pytest.fixture(scope="module")
def pair(one_chip):
    """The chip configuration cut to 2 layers, every width intact, as
    shapes on the described chip."""
    t = registry.get_model(configs.get_config(ARCH).replace(num_layers=2))
    d = registry.get_model(
        configs.get_draft_config(ARCH).replace(num_layers=2))
    place = lambda tree: jax.tree.map(
        lambda x: _sds(one_chip, x.shape, x.dtype), tree)
    key = _sds(one_chip, (2,), jnp.uint32)
    shapes = {}
    for name, api in (("t", t), ("d", d)):
        shapes[name + "params"] = place(jax.eval_shape(api.init, key))
        shapes[name + "pages"] = place(jax.eval_shape(
            lambda: api.init_paged_cache(NUM_BLOCKS, BLOCK)))
    return t, d, key, shapes


def _pool_bytes(pages):
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(pages))


def test_paged_spec_step_compiles_with_kernel(one_chip, pair, monkeypatch):
    """The real tier's speculative step, as compiled for the chip, runs the
    paged-attention kernel (not the gather oracle) and updates both pools
    in place: its temporaries stay far below one pool copy."""
    monkeypatch.setattr(ops, "kernel_mode", lambda: "compiled")
    t, d, key, sh = pair
    s = lambda *a: _sds(one_chip, *a)
    step = jax.jit(make_paged_spec_step(t, d), static_argnames=("gamma",),
                   donate_argnums=(3, 4))
    compiled = step.lower(
        key, sh["tparams"], sh["dparams"], sh["tpages"], sh["dpages"],
        s((B, MAX_PROMPT // BLOCK), jnp.int32), s((B,), jnp.int32),
        s((B,), jnp.int32), gamma=GAMMA_MAX).compile()
    assert "tpu_custom_call" in compiled.as_text()
    pools = _pool_bytes(sh["tpages"]) + _pool_bytes(sh["dpages"])
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes == pools
    assert ma.temp_size_in_bytes < pools // 2


def _slab_sized_ops(compiled, *pools):
    """Instructions of the compiled program whose result holds exactly one
    layer's slab of a pool, (NB+1, block_size, KH * hd) elements: a slice,
    layout change or transpose of the pool that the kernel would read
    instead of the pool itself."""
    slabs = {x.size // x.shape[0] for p in pools for x in jax.tree.leaves(p)}
    found = []
    for line in compiled.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = \w+\[([\d,]*)\]", line)
        if m is None:
            continue
        n = 1
        for d in m.group(1).split(","):
            n *= int(d) if d else 1
        if n in slabs:
            found.append(line.strip()[:120])
    return found


@pytest.mark.parametrize("step", ["ar", "spec"])
def test_paged_steps_read_the_pools_in_place(one_chip, pair, monkeypatch,
                                             step):
    """At the cell's batch and table width, the compiled decode (gamma 0)
    and speculative steps hand the kernel the stacked pools: no op writes
    a buffer of one layer's pool slab (a slice, layout copy or transpose
    of the pool), and the temporaries stay far below one slab."""
    monkeypatch.setattr(ops, "kernel_mode", lambda: "compiled")
    t, d, key, sh = pair
    s = lambda *a: _sds(one_chip, *a)
    tables = s((CELL_B, CELL_WIDTH), jnp.int32)
    rows = s((CELL_B,), jnp.int32)
    if step == "ar":
        fn = jax.jit(make_paged_ar_step(t), donate_argnums=(2,))
        compiled = fn.lower(key, sh["tparams"], sh["tpages"], tables, rows,
                            rows).compile()
    else:
        fn = jax.jit(make_paged_spec_step(t, d), static_argnames=("gamma",),
                     donate_argnums=(3, 4))
        compiled = fn.lower(key, sh["tparams"], sh["dparams"], sh["tpages"],
                            sh["dpages"], tables, rows, rows,
                            gamma=GAMMA_MAX).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _slab_sized_ops(compiled, sh["tpages"], sh["dpages"]) == []
    slab = min(x.size // x.shape[0] * x.dtype.itemsize
               for x in jax.tree.leaves(sh["dpages"]))
    assert compiled.memory_analysis().temp_size_in_bytes < slab // 4


def test_prefill_extension_fits(one_chip, pair, monkeypatch):
    """The longest prefill extension (B x 2048 tokens) unembeds only the
    rows it samples: its temporaries stay far below the (B, T, V) float32
    logits that a full unembedding would hold."""
    monkeypatch.setattr(ops, "kernel_mode", lambda: "compiled")
    t, _, _, sh = pair
    s = lambda *a: _sds(one_chip, *a)

    def extend(params, pages, tokens, tables, start, valid):
        logits, pages = t.decode_step_paged(params, pages, tokens, tables,
                                            start, valid, last_only=True)
        return jnp.argmax(logits[:, 0], axis=-1), pages

    compiled = jax.jit(extend, donate_argnums=(1,)).lower(
        sh["tparams"], sh["tpages"], s((B, MAX_PROMPT), jnp.int32),
        s((B, MAX_PROMPT // BLOCK), jnp.int32), s((B,), jnp.int32),
        s((B,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    full_logits = B * MAX_PROMPT * t.cfg.vocab_size * 4
    assert compiled.memory_analysis().temp_size_in_bytes < full_logits // 8
