"""Draft-then-verify speculative decoding step (chain-style, batched).

One jittable function per (target, draft) pair:

  spec_step(key, tparams, dparams, tcache, dcache, last_tokens, gamma)
    -> committed tokens, n_accepted, rolled-back caches

Cache-synchronisation invariant (holds before and after every step):
  tcache.length == dcache.length == N, both caches contain K/V (or SSM
  state) for tokens x_0..x_{N-1}, and ``last_tokens`` = x_N is committed but
  in NEITHER cache.  The draft chain therefore consumes the full
  (gamma+1)-token chunk [x_N, d_1..d_gamma] — one tiny extra draft step per
  round — so both caches advance in lockstep and rollback is a pure length
  decrement.

Attention caches roll back for free (stale slots are never attended: the
mask is pos <= q_position, and they are overwritten by later writes).
SSM/hybrid caches restore per-position state checkpoints (DESIGN.md §5) —
the TPU-friendly analogue of KV truncation.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, NamedTuple

import jax
import jax.numpy as jnp

from ..models.registry import ModelAPI
from .verify import verify_greedy, verify_rejection


class SpecResult(NamedTuple):
    tokens: jnp.ndarray       # (B, g+1) committed, -1 padded
    n_accepted: jnp.ndarray   # (B,)
    n_committed: jnp.ndarray  # (B,) == n_accepted + 1
    tcache: Any
    dcache: Any
    last_token: jnp.ndarray   # (B,) newly sampled token (not yet in caches)


def _select_ckpt(x, idx):
    """x: (T, L, B, ...) per-step checkpoints -> (L, B, ...) at per-seq idx."""
    T = x.shape[0]
    moved = jnp.moveaxis(x, 2, 0)  # (B, T, L, ...)
    sel = jax.vmap(lambda xb, i: xb[i])(moved, jnp.clip(idx, 0, T - 1))
    return jnp.moveaxis(sel, 0, 1)


def _rollback_ssm_cache(cache_ext, base_cache, n_keep):
    """Restore conv/ssm from checkpoint index n_keep-1 (state after consuming
    the first n_keep chunk tokens).  Attention parts (hybrid) roll back by
    length alone."""
    ck = cache_ext["checkpoints"]
    idx = n_keep - 1  # n_keep >= 1 always (chunk starts with the last token)
    out = {k: v for k, v in cache_ext.items() if k != "checkpoints"}
    out["conv"] = _select_ckpt(ck["conv"], idx)
    out["ssm"] = _select_ckpt(ck["ssm"], idx)
    out["length"] = base_cache["length"] + n_keep
    return out


def make_spec_step(target: ModelAPI, draft: ModelAPI, *, sampling: str = "greedy",
                   temperature: float = 1.0):
    """Build the jittable speculative-decoding step.

    sampling: "greedy" (accept on argmax match) or "rejection" (lossless
    stochastic verification).
    """
    t_is_ssm = target.cfg.family in ("ssm", "hybrid")
    d_is_ssm = draft.cfg.family in ("ssm", "hybrid")
    if draft.cfg.family == "hybrid":
        raise NotImplementedError("use a pure-ssm or attention draft model")

    def drafting(key, dparams, dcache, last_tokens, gamma: int):
        """Chain-draft. Consumes the full (gamma+1)-token chunk; returns the
        gamma proposals, their distributions, the advanced cache, and (for
        SSM drafts) per-step state checkpoints."""

        def body(carry, k):
            cache, tok = carry
            logits, cache = draft.decode_step(dparams, cache, tok[:, None])
            lg = logits[:, 0] / temperature
            if sampling == "greedy":
                nxt = jnp.argmax(lg, axis=-1)
            else:
                nxt = jax.random.categorical(k, lg)
            probs = jax.nn.softmax(lg, axis=-1)
            ck = (cache["conv"], cache["ssm"]) if d_is_ssm else None
            return (cache, nxt), (nxt, probs, ck)

        keys = jax.random.split(key, gamma + 1)
        (dcache, _), (toks, probs, cks) = jax.lax.scan(
            body, (dcache, last_tokens), keys)
        # proposals are the outputs of the first gamma consumes
        draft_tokens = toks[:gamma].T                     # (B, g)
        draft_probs = jnp.swapaxes(probs[:gamma], 0, 1)   # (B, g, V)
        return draft_tokens, draft_probs, dcache, cks

    def spec_step(key, tparams, dparams, tcache, dcache, last_tokens, gamma: int):
        """last_tokens: (B,). gamma: static python int > 0."""
        kd, kv = jax.random.split(key)
        draft_tokens, draft_probs, dcache_ext, dcks = drafting(
            kd, dparams, dcache, last_tokens, gamma)

        # target verifies [last, d_1..d_g] in one extension pass
        chunk = jnp.concatenate([last_tokens[:, None], draft_tokens], axis=1)
        t_logits, tcache_ext = target.decode_step(tparams, tcache, chunk)
        t_logits = t_logits / temperature

        if sampling == "greedy":
            res = verify_greedy(draft_tokens, t_logits)
        else:
            res = verify_rejection(kv, draft_tokens, draft_probs,
                                   jax.nn.softmax(t_logits, -1))
        n_acc = res["n_accepted"]
        n_keep = 1 + n_acc  # chunk tokens retained (x_N + accepted drafts)

        # --- target rollback
        if t_is_ssm:
            tcache_new = _rollback_ssm_cache(tcache_ext, tcache, n_keep)
        else:
            tcache_new = {k: v for k, v in tcache_ext.items()
                          if k != "checkpoints"}
            tcache_new["length"] = tcache["length"] + n_keep

        # --- draft rollback (consumed the same chunk + d_gamma)
        if d_is_ssm:
            conv_ck, ssm_ck = dcks
            dcache_new = dict(dcache_ext)
            dcache_new["conv"] = _select_ckpt(conv_ck, n_keep - 1)
            dcache_new["ssm"] = _select_ckpt(ssm_ck, n_keep - 1)
        else:
            dcache_new = dict(dcache_ext)
        dcache_new["length"] = tcache["length"] + n_keep

        return SpecResult(
            tokens=res["tokens"],
            n_accepted=n_acc,
            n_committed=n_acc + 1,
            tcache=tcache_new,
            dcache=dcache_new,
            last_token=res["next_token"],
        )

    return spec_step


def make_paged_spec_step(target: ModelAPI, draft: ModelAPI, *,
                         sampling: str = "greedy", temperature: float = 1.0):
    """Speculative step over paged KV pools (zero-copy continuous batching).

    Same chain-draft/verify semantics as :func:`make_spec_step`, but the
    caches are shared paged pools indexed by per-sequence block tables:

      spec_step(key, tparams, dparams, tpages, dpages, tables, lengths,
                last_tokens, gamma) -> SpecResult (tcache/dcache = pages)

    ``lengths`` is the per-sequence materialised token count N (the cache
    holds x_0..x_{N-1}; ``last_tokens`` = x_N is in neither pool).  Both
    models write the (gamma+1)-token chunk at positions N..N+gamma through
    the SAME block tables, so rollback is free: the host advances each
    sequence's length by n_accepted+1 and the stale slots beyond it are
    never attended (the kernel masks pos <= query position) and are
    overwritten by the next step's writes at the same positions.  The
    caller must have grown the tables to cover N + gamma + 1 positions
    (``BlockManager.ensure_capacity``)."""
    if not (target.supports_paged and draft.supports_paged):
        raise NotImplementedError(
            "paged speculative decoding needs attention-family target and "
            "draft models (SSM/hybrid state is O(1) — use make_spec_step)")

    def spec_step(key, tparams, dparams, tpages, dpages, tables, lengths,
                  last_tokens, gamma: int):
        kd, kv = jax.random.split(key)

        def body(carry, k):
            dpg, tok, pos = carry
            logits, dpg = draft.decode_step_paged(dparams, dpg, tok[:, None],
                                                  tables, pos)
            lg = logits[:, 0] / temperature
            if sampling == "greedy":
                nxt = jnp.argmax(lg, axis=-1)
            else:
                nxt = jax.random.categorical(k, lg)
            return (dpg, nxt, pos + 1), (nxt, jax.nn.softmax(lg, axis=-1))

        # named scopes tag each device op with its phase (profiler
        # metadata only: they change no computation)
        with jax.named_scope("draft"):
            keys = jax.random.split(kd, gamma + 1)
            (dpages, _, _), (toks, probs) = jax.lax.scan(
                body, (dpages, last_tokens, lengths), keys)
            draft_tokens = toks[:gamma].T                     # (B, g)
            draft_probs = jnp.swapaxes(probs[:gamma], 0, 1)   # (B, g, V)

        with jax.named_scope("verify"):
            # target verifies [last, d_1..d_g] in one paged extension pass
            chunk = jnp.concatenate([last_tokens[:, None], draft_tokens],
                                    axis=1)
            t_logits, tpages = target.decode_step_paged(
                tparams, tpages, chunk, tables, lengths)
            t_logits = t_logits / temperature

            if sampling == "greedy":
                res = verify_greedy(draft_tokens, t_logits)
            else:
                res = verify_rejection(kv, draft_tokens, draft_probs,
                                       jax.nn.softmax(t_logits, -1))
        n_acc = res["n_accepted"]
        return SpecResult(
            tokens=res["tokens"],
            n_accepted=n_acc,
            n_committed=n_acc + 1,
            tcache=tpages,
            dcache=dpages,
            last_token=res["next_token"],
        )

    return spec_step


def make_paged_ar_step(target: ModelAPI, *, sampling: str = "greedy",
                       temperature: float = 1.0):
    """Plain autoregressive decode step over the paged pool (gamma=0 arm)."""

    @jax.named_scope("decode")
    def ar_step(key, tparams, tpages, tables, lengths, last_tokens):
        logits, tpages = target.decode_step_paged(tparams, tpages,
                                                  last_tokens[:, None],
                                                  tables, lengths)
        lg = logits[:, 0] / temperature
        if sampling == "greedy":
            nxt = jnp.argmax(lg, axis=-1)
        else:
            nxt = jax.random.categorical(key, lg)
        return nxt, tpages

    return ar_step


def make_ar_step(target: ModelAPI, *, sampling: str = "greedy",
                 temperature: float = 1.0):
    """Plain autoregressive decode step (the gamma=0 arm)."""

    def ar_step(key, tparams, tcache, last_tokens):
        logits, tcache = target.decode_step(tparams, tcache, last_tokens[:, None])
        lg = logits[:, 0] / temperature
        if sampling == "greedy":
            nxt = jnp.argmax(lg, axis=-1)
        else:
            nxt = jax.random.categorical(key, lg)
        return nxt, tcache

    return ar_step
