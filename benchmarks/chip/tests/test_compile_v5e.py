"""Compile the benchmark's new pair, ``qwen3-14b-1chip``, for a described
TPU v5e, and the benchmark's own device programs (weight generator,
reference) for both configurations.

Nothing runs: the TPU compiler compiles for a chip that is described, not
attached, and refuses what the chip would refuse (tiling, VMEM, HBM).  The
topology is described inside a fixture, never at import.  Run by path:

  PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(CHIP.parents[1] / "src"))

from harness import models, reference, system  # noqa: E402

BLOCK, B, GAMMA_MAX, MAX_PROMPT = 16, 8, 5, 2048
QWEN = models.load_config("qwen3-14b-1chip")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back from the
        # persistent cache: keep it out of the cache
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", enabled)


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _pool_blocks():
    from repro.serving.costmodel import RooflineCostModel, TPU_V5E
    from repro.serving.paged_runtime import num_blocks_for
    return num_blocks_for(RooflineCostModel(TPU_V5E),
                          system.model_config(QWEN["target"]),
                          system.model_config(QWEN["draft"]), BLOCK)


def test_pool_size_is_the_computed_one():
    """1024 blocks of 16 tokens: what build_real_engine gives the pair."""
    assert _pool_blocks() == 1024


@pytest.mark.parametrize("T", [1, GAMMA_MAX + 1, MAX_PROMPT])
@pytest.mark.parametrize("which", ["target", "draft"])
def test_paged_attention_compiles(one_chip, which, T):
    from repro.kernels.paged_attention import paged_attention
    sp = QWEN[which]
    s = lambda *a: _sds(one_chip, *a)
    pages = s((_pool_blocks() + 1, BLOCK, sp["kv_heads"], sp["head_dim"]),
              jnp.bfloat16)
    compiled = jax.jit(paged_attention).lower(
        s((B, T, sp["heads"], sp["head_dim"]), jnp.bfloat16), pages, pages,
        s((B, 4096 // BLOCK), jnp.int32), s((B,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def pair(one_chip):
    """The pair cut to 2 layers, every width intact, as shapes."""
    from repro.models import registry
    t = registry.get_model(system.model_config(QWEN["target"]).replace(
        num_layers=2))
    d = registry.get_model(system.model_config(QWEN["draft"]).replace(
        num_layers=2))
    place = lambda tree: jax.tree.map(
        lambda x: _sds(one_chip, x.shape, x.dtype), tree)
    key = _sds(one_chip, (2,), jnp.uint32)
    nb = _pool_blocks()
    sh = {}
    for name, api in (("t", t), ("d", d)):
        sh[name + "params"] = place(jax.eval_shape(api.init, key))
        sh[name + "pages"] = place(jax.eval_shape(
            lambda: api.init_paged_cache(nb, BLOCK)))
    return t, d, key, sh


def _bytes(tree):
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def test_spec_step_compiles(one_chip, pair, monkeypatch):
    from repro.core.spec_decode import make_paged_spec_step
    from repro.kernels import ops
    monkeypatch.setattr(ops, "kernel_mode", lambda: "compiled")
    t, d, key, sh = pair
    s = lambda *a: _sds(one_chip, *a)
    step = jax.jit(make_paged_spec_step(t, d), static_argnames=("gamma",),
                   donate_argnums=(3, 4))
    compiled = step.lower(
        key, sh["tparams"], sh["dparams"], sh["tpages"], sh["dpages"],
        s((64, 4096 // BLOCK), jnp.int32), s((64,), jnp.int32),
        s((64,), jnp.int32), gamma=GAMMA_MAX).compile()
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    pools = _bytes(sh["tpages"]) + _bytes(sh["dpages"])
    assert ma.alias_size_in_bytes == pools
    assert ma.temp_size_in_bytes < pools // 2


def test_prefill_extension_compiles(one_chip, pair, monkeypatch):
    from repro.kernels import ops
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
    # the whole 10-layer stage's temporaries at B=8, T=2048 scale with the
    # layer count only through the carry: 2 layers must stay under 2 GB
    assert compiled.memory_analysis().temp_size_in_bytes < 2e9


@pytest.mark.parametrize("config", ["paper-7b-1chip", "qwen3-14b-1chip"])
def test_weight_generator_draws_in_place(one_chip, config):
    """One jitted call draws both models' bf16 weights with (almost) no
    temporaries: a float32 copy of the largest stacked weight would not fit
    beside the pool."""
    from weights import random as gen  # noqa: PLC0415
    conf = models.load_config(config)
    fn = gen._generator(tuple(sorted(conf["target"].items())),
                        tuple(sorted(conf["draft"].items())))
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                               sharding=one_chip)
    ma = fn.lower(key).compile().memory_analysis()
    assert ma.temp_size_in_bytes < 0.05 * ma.output_size_in_bytes


@pytest.mark.parametrize("config", ["paper-7b-1chip", "qwen3-14b-1chip"])
def test_reference_fits_beside_the_weights(one_chip, config):
    """The reference's forward over the longest served sequence (3072
    positions) needs well under the 16 GB that remain beside its bf16
    weights once the program's state is freed."""
    from weights import random as gen  # noqa: PLC0415
    sp = models.load_config(config)["target"]
    shapes = jax.eval_shape(lambda: gen._draw(jax.random.key(0), sp))
    place = jax.tree.map(lambda x: _sds(one_chip, x.shape, x.dtype), shapes)
    toks = _sds(one_chip, (3072,), jnp.int32)
    items = tuple(sorted(sp.items()))
    ma = reference._hidden.lower(items, False, place, toks).compile(
        ).memory_analysis()
    assert ma.temp_size_in_bytes < 3e9
    assert np.isfinite(ma.temp_size_in_bytes)
