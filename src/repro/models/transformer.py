"""Decoder-only transformer covering the dense / moe / vlm families.

Provides the uniform LM interface used by the registry:
  init_params(rng, cfg)                         -> params
  forward(cfg, params, batch)                   -> logits          (training)
  prefill(cfg, params, batch, max_len)          -> (last_logits, cache)
  decode_step(cfg, params, cache, tokens, pos)  -> (logits, cache)

Layer parameters are stacked on a leading axis and iterated with
``jax.lax.scan`` (MaxText-style) so 80-layer configs compile quickly; the KV
cache is likewise stacked ``(L, B, S, KH, hd)``.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from .common import (
    apply_mlp,
    apply_norm,
    attn_output,
    blockwise_attention,
    cache_write,
    decode_attention,
    embed_init,
    init_attention,
    init_mlp,
    init_norm,
    qkv_project,
)
from .moe import apply_moe, init_moe
from ..distributed.sharding import shard_activations

Params = Dict[str, Any]


def _dtype(cfg):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_layer(cfg, key):
    dt = _dtype(cfg)
    k_attn, k_mlp = jax.random.split(key)
    layer = {
        "attn": init_attention(cfg, k_attn, dt),
        "ln1": init_norm(cfg, cfg.d_model, dt),
        "ln2": init_norm(cfg, cfg.d_model, dt),
    }
    if cfg.moe_num_experts:
        layer["moe"] = init_moe(cfg, k_mlp, dt)
    else:
        layer["mlp"] = init_mlp(cfg, k_mlp, dt)
    return layer


def init_params(rng, cfg) -> Params:
    dt = _dtype(cfg)
    k_embed, k_layers, k_head, k_pos = jax.random.split(rng, 4)
    layer_keys = jax.random.split(k_layers, cfg.num_layers)
    if cfg.scan_layers:
        layers = jax.vmap(lambda k: init_layer(cfg, k))(layer_keys)
    else:
        layers = [init_layer(cfg, k) for k in layer_keys]
    params = {
        "embed": embed_init(k_embed, (cfg.vocab_size, cfg.d_model), dt),
        "layers": layers,
        "final_norm": init_norm(cfg, cfg.d_model, dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = embed_init(k_head, (cfg.vocab_size, cfg.d_model), dt)
    if cfg.pos_embedding == "learned":
        params["pos_embed"] = embed_init(k_pos, (cfg.max_position_embeddings, cfg.d_model), dt)
    if cfg.num_image_tokens:
        # stubbed modality frontend: a single projection applied to the
        # precomputed patch embeddings supplied by input_specs()
        params["image_proj"] = embed_init(k_pos, (cfg.d_model, cfg.d_model), dt)
    return params


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------


def embed_tokens(cfg, params, tokens):
    h = params["embed"][tokens]
    if cfg.embed_scale:
        h = h * jnp.asarray(cfg.d_model ** 0.5, h.dtype)
    return h


def embed_inputs(cfg, params, batch, *, positions):
    """tokens (+ optional image embeddings prefix) -> (B, S, d)."""
    h = embed_tokens(cfg, params, batch["tokens"])
    if cfg.num_image_tokens and "image_emb" in batch:
        img = batch["image_emb"].astype(h.dtype) @ params["image_proj"]
        h = jnp.concatenate([img, h], axis=1)
    if cfg.pos_embedding == "learned":
        h = h + params["pos_embed"][positions]
    return h


def unembed(cfg, params, h):
    table = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,vd->bsv", h, table, preferred_element_type=jnp.float32)
    if cfg.logit_softcap > 0.0:
        logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    return logits


# ---------------------------------------------------------------------------
# Layer application
# ---------------------------------------------------------------------------


def apply_layer_full(cfg, layer, h, positions, *, prefix_len=0):
    """Full-sequence (train / prefill) pass through one block. Returns (h, k, v)."""
    x = apply_norm(cfg, h, layer["ln1"])
    q, k, v = qkv_project(cfg, layer["attn"], x, positions)
    o = blockwise_attention(
        q, k, v, causal=True, prefix_len=prefix_len, chunk=cfg.attn_chunk,
        unroll=cfg.unroll_scans,
    )
    h = h + attn_output(layer["attn"], o)
    x = apply_norm(cfg, h, layer["ln2"])
    if cfg.moe_num_experts:
        y, _aux = apply_moe(cfg, layer["moe"], x)
    else:
        y = apply_mlp(cfg, layer["mlp"], x)
    return h + y, k, v


def apply_layer_decode(cfg, layer, h, k_cache, v_cache, positions):
    """Decode/extend: h (B, T, d); caches (B, S, KH, hd); positions (B, T).

    T=1 is plain autoregressive decode; T=gamma+1 is the speculative-verify
    extension.  New K/V are written into the cache at ``positions`` first,
    then every query attends to all cache slots at or before its position.
    """
    x = apply_norm(cfg, h, layer["ln1"])
    q, k, v = qkv_project(cfg, layer["attn"], x, positions)
    from ..distributed.sharding import replicate_new_kv, shard_kv_cache
    start = positions[:, 0]  # contiguous T-token span per sequence
    k_cache = shard_kv_cache(cache_write(k_cache, replicate_new_kv(k), start))
    v_cache = shard_kv_cache(cache_write(v_cache, replicate_new_kv(v), start))
    o = decode_attention(q, k_cache, v_cache, positions)
    h = h + attn_output(layer["attn"], o)
    x = apply_norm(cfg, h, layer["ln2"])
    if cfg.moe_num_experts:
        y, _aux = apply_moe(cfg, layer["moe"], x,
                            capacity_factor=max(cfg.moe_capacity_factor, 2.0))
    else:
        y = apply_mlp(cfg, layer["mlp"], x)
    return h + y, k_cache, v_cache


def _maybe_remat(cfg, fn):
    if cfg.remat == "full":
        return jax.checkpoint(fn)
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    return fn


# ---------------------------------------------------------------------------
# Full forward (training)
# ---------------------------------------------------------------------------


def forward(cfg, params, batch) -> jnp.ndarray:
    tokens = batch["tokens"]
    B, S_txt = tokens.shape
    S = S_txt + (cfg.num_image_tokens if "image_emb" in batch else 0)
    positions = jnp.arange(S)[None, :]
    h = embed_inputs(cfg, params, batch, positions=positions)
    prefix = cfg.num_image_tokens if "image_emb" in batch else 0

    h = shard_activations(h)
    if cfg.scan_layers:
        step = _maybe_remat(cfg, lambda hh, layer: (
            shard_activations(apply_layer_full(
                cfg, layer, hh, positions, prefix_len=prefix)[0]), None))
        h, _ = jax.lax.scan(step, h, params["layers"])
    else:
        blk = _maybe_remat(cfg, lambda hh, layer: shard_activations(
            apply_layer_full(cfg, layer, hh, positions, prefix_len=prefix)[0]))
        for layer in params["layers"]:
            h = blk(h, layer)
    h = apply_norm(cfg, h, params["final_norm"])
    return h  # hidden states; loss fn does streamed unembed+xent


def logits_from_hidden(cfg, params, h):
    return unembed(cfg, params, h)


# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------


def init_cache(cfg, batch_size: int, max_len: int):
    dt = _dtype(cfg)
    KH, hd, L = cfg.num_kv_heads, cfg.resolved_head_dim, cfg.num_layers
    return {
        "k": jnp.zeros((L, batch_size, max_len, KH, hd), dt),
        "v": jnp.zeros((L, batch_size, max_len, KH, hd), dt),
        "length": jnp.zeros((batch_size,), jnp.int32),
    }


def prefill(cfg, params, batch, max_len: int):
    """Run the full prompt, returning last-position logits and a filled cache."""
    tokens = batch["tokens"]
    B, S_txt = tokens.shape
    S = S_txt + (cfg.num_image_tokens if "image_emb" in batch else 0)
    positions = jnp.arange(S)[None, :]
    h = embed_inputs(cfg, params, batch, positions=positions)
    prefix = cfg.num_image_tokens if "image_emb" in batch else 0

    cache = init_cache(cfg, B, max_len)

    def body(hh, xs):
        layer = xs
        hh, k, v = apply_layer_full(cfg, layer, hh, positions, prefix_len=prefix)
        return shard_activations(hh), (k, v)

    if cfg.scan_layers:
        h, (ks, vs) = jax.lax.scan(body, h, params["layers"])
    else:
        ks_list, vs_list = [], []
        for layer in params["layers"]:
            h, k, v = apply_layer_full(cfg, layer, h, positions, prefix_len=prefix)
            ks_list.append(k)
            vs_list.append(v)
        ks, vs = jnp.stack(ks_list), jnp.stack(vs_list)

    cache["k"] = jax.lax.dynamic_update_slice(
        cache["k"], ks.astype(cache["k"].dtype), (0, 0, 0, 0, 0))
    cache["v"] = jax.lax.dynamic_update_slice(
        cache["v"], vs.astype(cache["v"].dtype), (0, 0, 0, 0, 0))
    cache["length"] = jnp.full((B,), S, jnp.int32)
    h = apply_norm(cfg, h, params["final_norm"])
    return unembed(cfg, params, h[:, -1:, :]), cache


# ---------------------------------------------------------------------------
# Paged KV cache (real serving backend)
# ---------------------------------------------------------------------------


def init_paged_cache(cfg, num_blocks: int, block_size: int):
    """Paged KV pool: (L, num_blocks + 1, block_size, KH * hd).

    Each token's heads sit side by side in the last axis, so a block is a
    lane-dense (block_size, KH * hd) tile for every head size — the
    block-migration kernel moves it with one DMA.  The LAST block (index
    ``num_blocks``) is the write-off ("trash") block: padded batch rows and
    ragged-chunk tail slots scatter their K/V there so no write can ever
    touch a live sequence's blocks.  Block tables are padded with the trash
    id too, which doubles as the "any valid id" padding the attention
    kernels require."""
    dt = _dtype(cfg)
    KH, hd, L = cfg.num_kv_heads, cfg.resolved_head_dim, cfg.num_layers
    shape = (L, num_blocks + 1, block_size, KH * hd)
    return {"k_pages": jnp.zeros(shape, dt), "v_pages": jnp.zeros(shape, dt)}


def _paged_write(pages, layer, new, tables, positions, valid):
    """Scatter ``new`` (B, T, KH, hd) into layer ``layer`` of ``pages``
    (L, NB+1, bs, KH * hd) at per-token ``positions`` (B, T) through the
    block tables; slots where ``valid`` is False are routed to the trash
    block.  The scatter updates the stacked pool in place."""
    B, T = positions.shape
    bs = pages.shape[2]
    maxb = tables.shape[1]
    idx = jnp.minimum(positions // bs, maxb - 1)
    blk = jnp.take_along_axis(tables, idx, axis=1)      # (B, T)
    blk = jnp.where(valid, blk, pages.shape[1] - 1)     # trash for pad slots
    off = positions % bs
    return pages.at[layer, blk, off].set(
        new.reshape(B, T, -1).astype(pages.dtype))


def apply_layer_decode_paged(cfg, layer, h, pages, i, tables, positions,
                             valid, lengths):
    """Paged analogue of :func:`apply_layer_decode` for layer ``i``: h
    (B, T, d); pages {"k_pages", "v_pages"} (L, NB+1, bs, KH * hd).  New
    K/V are scattered through the block tables first, then every query
    attends to all paged slots at or before its position (the multi-query
    paged-attention kernel, which reads layer ``i`` of the whole pools in
    place, or its jnp oracle off the TPU — ``kernels.ops`` decides)."""
    from ..kernels import ops
    x = apply_norm(cfg, h, layer["ln1"])
    q, k, v = qkv_project(cfg, layer["attn"], x, positions)
    kp = _paged_write(pages["k_pages"], i, k, tables, positions, valid)
    vp = _paged_write(pages["v_pages"], i, v, tables, positions, valid)
    o = ops.paged_attention(q, kp, vp, i, tables, lengths)
    h = h + attn_output(layer["attn"], o.astype(h.dtype))
    x = apply_norm(cfg, h, layer["ln2"])
    if cfg.moe_num_experts:
        y, _aux = apply_moe(cfg, layer["moe"], x,
                            capacity_factor=max(cfg.moe_capacity_factor, 2.0))
    else:
        y = apply_mlp(cfg, layer["mlp"], x)
    return h + y, {"k_pages": kp, "v_pages": vp}


def decode_step_paged(cfg, params, pages, tokens, tables, start, valid=None,
                      *, last_only: bool = False):
    """Extend T tokens per sequence against the paged KV pool.

    One function serves every real-backend shape: plain decode (T=1),
    speculative verification (T=gamma+1), batched prefill (start=0) and
    chunked-prefill appends (ragged ``valid``).

    tokens: (B, T) int32; tables: (B, max_blocks) int32 block tables padded
    with the trash id; start: (B,) tokens already materialised per sequence;
    valid: (B,) count of real tokens per row (None = all T valid).  Invalid
    tail slots write their K/V to the trash block and produce garbage logits
    — callers read logits at index ``valid - 1``.  The pages are carried
    through the layer loop and updated in place.  Returns
    (logits (B, T, V), pages); with ``last_only`` only the row at
    ``valid - 1`` is unembedded and the logits are (B, 1, V) — a prefill
    extension never materialises (B, T, V) float32 logits."""
    B, T = tokens.shape
    positions = start[:, None] + jnp.arange(T)[None, :]            # (B, T)
    if valid is None:
        vmask = jnp.ones((B, T), bool)
    else:
        vmask = jnp.arange(T)[None, :] < valid[:, None]
    lengths = start + T
    h = embed_tokens(cfg, params, tokens)
    if cfg.pos_embedding == "learned":
        h = h + params["pos_embed"][positions]

    if cfg.scan_layers:
        def body(carry, layer):
            hh, pg, i = carry
            hh, pg = apply_layer_decode_paged(cfg, layer, hh, pg, i, tables,
                                              positions, vmask, lengths)
            return (hh, pg, i + 1), None
        (h, pages, _), _ = jax.lax.scan(
            body, (h, pages, jnp.int32(0)), params["layers"])
    else:
        for i, layer in enumerate(params["layers"]):
            h, pages = apply_layer_decode_paged(cfg, layer, h, pages, i,
                                                tables, positions, vmask,
                                                lengths)
    if last_only:
        last = T - 1 if valid is None else jnp.maximum(valid - 1, 0)
        idx = jnp.broadcast_to(jnp.asarray(last), (B,))[:, None, None]
        h = jnp.take_along_axis(h, idx, axis=1)                    # (B, 1, d)
    h = apply_norm(cfg, h, params["final_norm"])
    return unembed(cfg, params, h), pages


def decode_step(cfg, params, cache, tokens, positions=None):
    """Extend by T tokens: tokens (B, T) int32; T=1 is plain decode and
    T=gamma+1 is the speculative-verify extension.  Positions default to a
    contiguous span starting at cache['length']."""
    B, T = tokens.shape
    if positions is None:
        positions = cache["length"][:, None] + jnp.arange(T)[None, :]  # (B, T)
    h = embed_tokens(cfg, params, tokens)
    if cfg.pos_embedding == "learned":
        h = h + params["pos_embed"][positions]

    if cfg.scan_layers:
        def body(hh, xs):
            layer, kc, vc = xs
            hh, kc, vc = apply_layer_decode(cfg, layer, hh, kc, vc, positions)
            return hh, (kc, vc)
        h, (ks, vs) = jax.lax.scan(body, h, (params["layers"], cache["k"], cache["v"]))
        cache = {"k": ks, "v": vs, "length": cache["length"] + T}
    else:
        ks_l, vs_l = [], []
        for i, layer in enumerate(params["layers"]):
            h, kc, vc = apply_layer_decode(
                cfg, layer, h, cache["k"][i], cache["v"][i], positions)
            ks_l.append(kc)
            vs_l.append(vc)
        cache = {"k": jnp.stack(ks_l), "v": jnp.stack(vs_l),
                 "length": cache["length"] + T}
    h = apply_norm(cfg, h, params["final_norm"])
    return unembed(cfg, params, h), cache
