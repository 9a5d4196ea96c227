"""Config registry: assigned architectures, shapes, draft pairings,
ShapeDtypeStruct input specs, and reduced configs for CPU smoke tests."""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .base import (
    ALL_SHAPES,
    DECODE_32K,
    LONG_500K,
    PREFILL_32K,
    TRAIN_4K,
    ModelConfig,
    ParallelConfig,
    ShapeConfig,
    shapes_for,
)
from . import (
    deepseek_7b,
    gemma_7b,
    granite_moe_1b,
    grok1_314b,
    mamba2_780m,
    paligemma_3b,
    paper_7b,
    qwen2_72b,
    qwen3_14b,
    whisper_medium,
    zamba2_1p2b,
)

_MODULES = {
    "whisper-medium": whisper_medium,
    "deepseek-7b": deepseek_7b,
    "gemma-7b": gemma_7b,
    "qwen2-72b": qwen2_72b,
    "qwen3-14b": qwen3_14b,
    "grok-1-314b": grok1_314b,
    "granite-moe-1b-a400m": granite_moe_1b,
    "zamba2-1.2b": zamba2_1p2b,
    "paligemma-3b": paligemma_3b,
    "mamba2-780m": mamba2_780m,
    "paper-7b": paper_7b,
}

ASSIGNED_ARCHS: Tuple[str, ...] = tuple(k for k in _MODULES if k != "paper-7b")

# arch name -> (target, draft); cuts of a pair for a chip are registered
# beside the published configurations under their own names
_PAIRS: Dict[str, Tuple[ModelConfig, ModelConfig]] = {
    name: (mod.CONFIG, mod.DRAFT) for name, mod in _MODULES.items()}
_PAIRS["paper-7b-1chip"] = (paper_7b.ONE_CHIP, paper_7b.DRAFT)


def list_archs():
    return list(_PAIRS)


def get_config(name: str) -> ModelConfig:
    return _PAIRS[name][0]


def get_draft_config(name: str) -> ModelConfig:
    return _PAIRS[name][1]


def get_shape(name: str) -> ShapeConfig:
    for s in ALL_SHAPES:
        if s.name == name:
            return s
    raise KeyError(name)


# ---------------------------------------------------------------------------
# Reduced configs (smoke tests): same family/features, tiny dimensions
# ---------------------------------------------------------------------------


def reduced(cfg: ModelConfig) -> ModelConfig:
    kw = dict(
        num_layers=min(cfg.num_layers, 2),
        d_model=128,
        vocab_size=256,
        max_position_embeddings=512,
        attn_chunk=64,
        xent_chunk=64,
    )
    if cfg.num_heads:
        kw.update(num_heads=4, num_kv_heads=min(cfg.num_kv_heads, 4) if
                  cfg.num_kv_heads > 1 else 1)
        kw["num_kv_heads"] = 1 if cfg.num_kv_heads == 1 else (
            2 if cfg.num_kv_heads < cfg.num_heads else 4)
        kw["head_dim"] = 32 if cfg.head_dim else 0
    if cfg.d_ff:
        kw["d_ff"] = 256 if not cfg.moe_num_experts else 64
    if cfg.moe_num_experts:
        # cf=8: no capacity drops at smoke scale, so prefill+decode is exactly
        # equivalent to the full forward (dropping is batch-composition
        # dependent by design and tested separately)
        kw.update(moe_num_experts=min(cfg.moe_num_experts, 4),
                  moe_top_k=min(cfg.moe_top_k, 2),
                  moe_capacity_factor=8.0)
    if cfg.ssm_state:
        kw.update(ssm_state=16, ssm_headdim=32, ssm_chunk=32)
    if cfg.family == "encdec":
        kw.update(enc_layers=2, dec_layers=2, enc_context=32)
    if cfg.family == "hybrid":
        kw.update(num_layers=4, hybrid_attn_every=2)
    if cfg.num_image_tokens:
        kw["num_image_tokens"] = 8
    return cfg.replace(**kw)


# ---------------------------------------------------------------------------
# input_specs — ShapeDtypeStruct stand-ins for every model input
# ---------------------------------------------------------------------------


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def input_specs(cfg: ModelConfig, shape: ShapeConfig, *, batch_override=None) -> Dict:
    """Returns the kwargs pytree for the step function of this (arch, shape).

    train  -> {"batch": {tokens, labels[, enc_emb | image_emb]}}
    prefill-> {"batch": {tokens[, enc_emb | image_emb]}}
    decode -> {"cache": <cache specs>, "tokens": (B, 1)}
    """
    B = batch_override or shape.global_batch
    S = shape.seq_len
    i32, bf16 = jnp.int32, jnp.bfloat16

    if shape.kind in ("train", "prefill"):
        batch = {}
        if cfg.family == "encdec":
            dec_len = S if shape.kind == "train" else max(S // 4, 1)
            batch["enc_emb"] = _sds((B, S, cfg.d_model), bf16)
            batch["tokens"] = _sds((B, dec_len), i32)
            if shape.kind == "train":
                batch["labels"] = _sds((B, dec_len), i32)
        elif cfg.family == "vlm":
            n_img = cfg.num_image_tokens
            batch["image_emb"] = _sds((B, n_img, cfg.d_model), bf16)
            batch["tokens"] = _sds((B, S - n_img), i32)
            if shape.kind == "train":
                batch["labels"] = _sds((B, S - n_img), i32)
        else:
            batch["tokens"] = _sds((B, S), i32)
            if shape.kind == "train":
                batch["labels"] = _sds((B, S), i32)
        return {"batch": batch}

    # decode: cache filled to S, one new token
    from ..models import registry as _registry  # local import to avoid cycle

    api = _registry.get_model(cfg)
    if cfg.family == "encdec":
        cache = jax.eval_shape(lambda: api.init_cache(B, S, enc_len=cfg.enc_context))
    else:
        cache = jax.eval_shape(lambda: api.init_cache(B, S))
    return {"cache": cache, "tokens": _sds((B, 1), i32)}
