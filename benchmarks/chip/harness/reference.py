"""The plain reference, in straightforward ``jax.numpy``, float32 at
"highest" matmul precision, with no kernels, cache or batching.  It imports
nothing of the program and reads only the benchmark's own weights, laid out
as ``weights/random.py`` draws them.

A model's equations are its architecture module's (``archs/<name>.py``,
``hidden`` and ``logits``), written from the primitives here that every
layout shares: the matmul, RMSNorm, rotary embedding and causal attention.
This file holds the comparison over served tokens.

``quant=True`` computes every matmul in simulated int8 (weights per output
channel, activations per row, symmetric, dequantized to float32): the
control, one precision below the configuration's bfloat16.

Sequences are computed one at a time, padded at the end to a multiple of
``PAD`` tokens (causal attention makes the padding invisible to earlier
positions); queries go in blocks of ``Q_BLOCK``, so the working set stays
near 1 GB at the served widths."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .models import arch

PAD = 1024
Q_BLOCK = 512
V_BLOCK = 256          # logit rows computed at a time
HI = jax.lax.Precision.HIGHEST


def _fq(x, axis):
    """Symmetric int8 fake quantization of ``x`` along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def mm(x, w, quant: bool):
    """x (..., K) @ w (K, N) in float32; w arrives in bf16."""
    w = w.astype(jnp.float32)
    if quant:
        x = _fq(x, -1)
        w = _fq(w, 0)
    return jnp.matmul(x, w, precision=HI)


def rms(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * w.astype(jnp.float32)


def rope(x, theta):
    """x: (S, heads, hd) at positions 0..S-1."""
    S, _, hd = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs      # (S, hd/2)
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v):
    """Causal softmax attention of q (S, H, hd) over k, v (S, KH, hd) with
    H / KH query heads to each KV head, scaled by 1 / sqrt(hd); (S, H, hd)."""
    S, H, hd = q.shape
    G = H // k.shape[1]
    kg = jnp.repeat(k, G, axis=1)                       # (S, H, hd)
    vg = jnp.repeat(v, G, axis=1)
    outs = []
    for q0 in range(0, S, Q_BLOCK):
        qb = q[q0:q0 + Q_BLOCK]                         # (Qb, H, hd)
        sc = jnp.einsum("qhd,khd->hqk", qb, kg, precision=HI) * hd ** -0.5
        qpos = q0 + jnp.arange(qb.shape[0])
        mask = qpos[:, None] >= jnp.arange(S)[None, :]
        sc = jnp.where(mask[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, vg, precision=HI))
    return jnp.concatenate(outs, 0)


@functools.partial(jax.jit, static_argnames=("spec_items", "quant"))
def _hidden(spec_items, quant, params, tokens):
    s = dict(spec_items)
    return arch(s).hidden(s, quant, params, tokens)


@functools.partial(jax.jit, static_argnames=("spec_items", "quant"))
def _logits(spec_items, quant, params, rows):
    s = dict(spec_items)
    return arch(s).logits(s, quant, params, rows)


def _padded(tokens) -> np.ndarray:
    n = len(tokens)
    out = np.zeros(-(-n // PAD) * PAD, np.int32)
    out[:n] = tokens
    return out


def served_gaps(spec: dict, params, prompt, served, *, quant_control=False):
    """Gaps of one served sequence, in the float32 reference's logits.

    ``served`` are the tokens the program served after ``prompt``; the
    i-th was chosen at position P - 1 + i.  Returns (gaps, control_gaps):
    for each served token, the reference's best logit less the reference's
    logit of that token (0 where they agree), over the standard deviation
    of the reference's logits at that position, so that one limit reads
    alike at every width; and with ``quant_control`` the same for the
    token the int8 control puts first at that position (None otherwise)."""
    items = tuple(sorted(spec.items()))
    P, n = len(prompt), len(served)
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(served[:-1], np.int32)])
    toks = _padded(seq)
    pos = np.arange(P - 1, P - 1 + n)
    with jax.default_matmul_precision("highest"):
        h = _hidden(items, False, params, toks)
        hq = _hidden(items, True, params, toks) if quant_control else None
        gaps, cgaps = [], []
        for b0 in range(0, n, V_BLOCK):
            idx = pos[b0:b0 + V_BLOCK]
            rows = np.zeros(V_BLOCK, np.int64)
            rows[:len(idx)] = idx
            ref = np.asarray(_logits(items, False, params, h[rows]))[:len(idx)]
            best, sd = ref.max(-1), ref.std(-1)
            tok = np.asarray(served[b0:b0 + V_BLOCK])
            gaps.append((best - ref[np.arange(len(idx)), tok]) / sd)
            if quant_control:
                ctl = np.asarray(_logits(items, True, params, hq[rows]))
                pick = ctl[:len(idx)].argmax(-1)
                cgaps.append((best - ref[np.arange(len(idx)), pick]) / sd)
    return (np.concatenate(gaps),
            np.concatenate(cgaps) if quant_control else None)
