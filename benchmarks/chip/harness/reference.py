"""The plain reference: a dense decoder (Qwen2 / Qwen3 layout) written in
straightforward ``jax.numpy``, float32 at "highest" matmul precision, with
no kernels, cache or batching.  It imports nothing of the program and reads
only the benchmark's own weights, laid out as ``weights/random.py`` draws
them.

Equations (per layer, pre-norm residual):
  x  = rmsnorm(h) * ln1;  q, k, v = x Wq (+bq), x Wk (+bk), x Wv (+bv)
  q, k = rmsnorm_per_head(q) * q_norm, ... (Qwen3 only), then rotary
  embedding (half-split rotation, theta from the config) at positions 0..S-1
  o  = softmax(q k^T / sqrt(hd), causal) v  with grouped KV heads;  h += o Wo
  x  = rmsnorm(h) * ln2;  h += (silu(x Wg) * (x Wu)) Wd
  logits = rmsnorm(h) * final_norm  @ (lm_head or embed)^T

``quant=True`` computes every matmul in simulated int8 (weights per output
channel, activations per row, symmetric, dequantized to float32): the
control, one precision below the configuration's bfloat16.

Sequences are computed one at a time, padded at the end to a multiple of
``PAD`` tokens (causal attention makes the padding invisible to earlier
positions); queries go in blocks of ``Q_BLOCK`` and the SwiGLU in
``FF_CHUNKS`` column chunks, so the working set stays near 1 GB at the
served widths."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PAD = 1024
Q_BLOCK = 512
FF_CHUNKS = 4
V_BLOCK = 256          # logit rows computed at a time
HI = jax.lax.Precision.HIGHEST


def _fq(x, axis):
    """Symmetric int8 fake quantization of ``x`` along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _mm(x, w, quant: bool):
    """x (..., K) @ w (K, N) in float32; w arrives in bf16."""
    w = w.astype(jnp.float32)
    if quant:
        x = _fq(x, -1)
        w = _fq(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * w.astype(jnp.float32)


def _rope(x, theta):
    """x: (S, heads, hd) at positions 0..S-1."""
    S, _, hd = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs      # (S, hd/2)
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(s, quant, h, lw):
    S, d = h.shape
    H, KH, hd, eps = s["heads"], s["kv_heads"], s["head_dim"], s["norm_eps"]
    a = lw["attn"]
    x = _rms(h, lw["ln1"]["scale"], eps)
    q = _mm(x, a["wq"].reshape(d, H * hd), quant).reshape(S, H, hd)
    k = _mm(x, a["wk"].reshape(d, KH * hd), quant).reshape(S, KH, hd)
    v = _mm(x, a["wv"].reshape(d, KH * hd), quant).reshape(S, KH, hd)
    if "bq" in a:
        q = q + a["bq"].astype(jnp.float32)
        k = k + a["bk"].astype(jnp.float32)
        v = v + a["bv"].astype(jnp.float32)
    if "q_norm" in a:
        q = _rms(q, a["q_norm"], eps)
        k = _rms(k, a["k_norm"], eps)
    q, k = _rope(q, s["rope_theta"]), _rope(k, s["rope_theta"])
    G = H // KH
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
    o = jnp.concatenate(outs, 0).reshape(S, H * hd)
    h = h + _mm(o, a["wo"].reshape(H * hd, d), quant)
    x = _rms(h, lw["ln2"]["scale"], eps)
    m = lw["mlp"]
    f = s["d_ff"]
    c = -(-f // FF_CHUNKS)
    y = jnp.zeros_like(h)
    for f0 in range(0, f, c):
        g = _mm(x, m["wg"][:, f0:f0 + c], quant)
        u = _mm(x, m["wu"][:, f0:f0 + c], quant)
        y = y + _mm(jax.nn.silu(g) * u, m["wd"][f0:f0 + c], quant)
    return h + y


@functools.partial(jax.jit, static_argnames=("spec_items", "quant"))
def _hidden(spec_items, quant, params, tokens):
    s = dict(spec_items)
    h = params["embed"][tokens].astype(jnp.float32)

    def body(hh, lw):
        return _layer(s, quant, hh, lw), None
    h, _ = jax.lax.scan(body, h, params["layers"])
    return _rms(h, params["final_norm"]["scale"], s["norm_eps"])


@functools.partial(jax.jit, static_argnames=("spec_items", "quant"))
def _logits(spec_items, quant, params, rows):
    s = dict(spec_items)
    table = params["embed"] if s["tie"] else params["lm_head"]   # (V, d)
    return _mm(rows, table.T, quant)


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
