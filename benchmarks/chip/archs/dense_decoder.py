"""Architecture module ``dense_decoder``: the dense Qwen2 / Qwen3 decoder
(GQA attention with rotary embedding, optional qkv bias (Qwen2) and
per-head q/k RMSNorm (Qwen3), SwiGLU feed-forward, RMSNorm pre-norm).

Everything the harness knows of this layout is here: the spec read from a
configuration file's Hugging Face keys, its CPU rehearsal cut, the
program's ``ModelConfig`` fields, the parameter tree the weight generator
draws, the float32 reference's forward pass, and the operation and byte
counts.  Keys of layouts this module does not compute are refused, so that
no other layout is measured as this one.

Reference equations (per layer, pre-norm residual):
  x  = rmsnorm(h) * ln1;  q, k, v = x Wq (+bq), x Wk (+bk), x Wv (+bv)
  q, k = rmsnorm_per_head(q) * q_norm, ... (Qwen3 only), then rotary
  embedding (half-split rotation, theta from the config) at positions 0..S-1
  o  = softmax(q k^T / sqrt(hd), causal) v  with grouped KV heads;  h += o Wo
  x  = rmsnorm(h) * ln2;  h += (silu(x Wg) * (x Wu)) Wd
  logits = rmsnorm(h) * final_norm  @ (lm_head or embed)^T
The SwiGLU goes in ``FF_CHUNKS`` column chunks, so that the working set
stays near 1 GB at the served widths."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from harness.counts import BF16
from harness.reference import attention, mm, rms, rope

FF_CHUNKS = 4

# keys of layouts this module does not compute: experts, latent attention
_REFUSED = ("num_experts", "n_routed_experts", "moe_intermediate_size",
            "kv_lora_rank")


def _refuse(name: str, c: dict) -> None:
    for key in _REFUSED:
        if key in c:
            raise ValueError(f"{name}: the dense decoder does not compute "
                             f"{key!r}")
    other = sorted(set(c.get("layer_types") or ()) - {"full_attention"})
    if other:
        with_window = (" (with 'sliding_window')" if "sliding_window" in c
                       else "")
        raise ValueError(f"{name}: the dense decoder attends every key; "
                         f"'layer_types' holds {other}{with_window}")
    if c.get("use_sliding_window"):
        raise ValueError(f"{name}: the dense decoder attends every key; "
                         "'use_sliding_window' is set")
    if c.get("hidden_act", "silu") != "silu":
        raise ValueError(f"{name}: 'hidden_act' is {c['hidden_act']!r}; the "
                         "dense decoder computes SwiGLU (silu) only")


def spec(name: str, c: dict) -> dict:
    _refuse(name, c)
    return {
        "name": name,
        "layers": int(c["num_hidden_layers"]),
        "d_model": int(c["hidden_size"]),
        "heads": int(c["num_attention_heads"]),
        "kv_heads": int(c["num_key_value_heads"]),
        "head_dim": int(c.get("head_dim")
                        or c["hidden_size"] // c["num_attention_heads"]),
        "d_ff": int(c["intermediate_size"]),
        "vocab": int(c["vocab_size"]),
        "qkv_bias": bool(c.get("qkv_bias", False)),
        "qk_norm": bool(c.get("qk_norm", False)),
        "rope_theta": float(c["rope_theta"]),
        "tie": bool(c["tie_word_embeddings"]),
        "norm_eps": float(c.get("rms_norm_eps", 1e-6)),
    }


def rehearsal(s: dict) -> dict:
    """The CPU rehearsal's cut: the repository's reduced preset (2 layers,
    hidden 128, 4 heads of 32, SwiGLU 256, vocabulary 256), kept with the
    benchmark so that the rehearsal's weights are its own."""
    out = dict(s, layers=min(s["layers"], 2), d_model=128, heads=4,
               d_ff=256, vocab=256, head_dim=32)
    out["kv_heads"] = (1 if s["kv_heads"] == 1 else
                       2 if s["kv_heads"] < s["heads"] else 4)
    return out


def program_fields(s: dict) -> dict:
    """The program's ``ModelConfig`` fields of this spec."""
    return {"num_layers": s["layers"], "d_model": s["d_model"],
            "num_heads": s["heads"], "num_kv_heads": s["kv_heads"],
            "d_ff": s["d_ff"], "vocab_size": s["vocab"],
            "qkv_bias": s["qkv_bias"], "qk_norm": s["qk_norm"],
            "rope_theta": s["rope_theta"], "tie_embeddings": s["tie"],
            "family": "dense", "mlp_type": "swiglu", "norm_type": "rmsnorm",
            "rmsnorm_offset": False, "embed_scale": False,
            "logit_softcap": 0.0, "pos_embedding": "rope",
            "dtype": "bfloat16", "moe_num_experts": 0,
            "head_dim": s["head_dim"]}


# -- parameters --------------------------------------------------------------

def param_shapes(s: dict) -> dict:
    """The tree as the serving program reads it: layers stacked on a
    leading axis, attention weights (d, heads, head_dim)."""
    L, d, H, KH, hd, f, V = (s["layers"], s["d_model"], s["heads"],
                             s["kv_heads"], s["head_dim"], s["d_ff"],
                             s["vocab"])
    attn = {"wq": (L, d, H, hd), "wk": (L, d, KH, hd), "wv": (L, d, KH, hd),
            "wo": (L, H, hd, d)}
    if s["qkv_bias"]:
        attn.update(bq=(L, H, hd), bk=(L, KH, hd), bv=(L, KH, hd))
    if s["qk_norm"]:
        attn.update(q_norm=(L, hd), k_norm=(L, hd))
    tree = {
        "embed": (V, d),
        "layers": {"attn": attn, "ln1": {"scale": (L, d)},
                   "ln2": {"scale": (L, d)},
                   "mlp": {"wg": (L, d, f), "wu": (L, d, f), "wd": (L, f, d)}},
        "final_norm": {"scale": (d,)},
    }
    if not s["tie"]:
        tree["lm_head"] = (V, d)
    return tree


def param_scale(path: str, s: dict):
    """(mean, std) of one parameter: fan-in scaling for projections, 0.02
    for embeddings and biases, 1 +- 0.1 for norm scales."""
    leaf = path.rsplit("/", 1)[-1]
    if leaf in ("scale", "q_norm", "k_norm"):
        return 1.0, 0.1
    if leaf in ("embed", "lm_head", "bq", "bk", "bv"):
        return 0.0, 0.02
    fan_in = {"wq": s["d_model"], "wk": s["d_model"], "wv": s["d_model"],
              "wo": s["heads"] * s["head_dim"], "wg": s["d_model"],
              "wu": s["d_model"], "wd": s["d_ff"]}[leaf]
    return 0.0, fan_in ** -0.5


# -- the float32 reference -----------------------------------------------------

def _layer(s, quant, h, lw):
    S, d = h.shape
    H, KH, hd, eps = s["heads"], s["kv_heads"], s["head_dim"], s["norm_eps"]
    a = lw["attn"]
    x = rms(h, lw["ln1"]["scale"], eps)
    q = mm(x, a["wq"].reshape(d, H * hd), quant).reshape(S, H, hd)
    k = mm(x, a["wk"].reshape(d, KH * hd), quant).reshape(S, KH, hd)
    v = mm(x, a["wv"].reshape(d, KH * hd), quant).reshape(S, KH, hd)
    if "bq" in a:
        q = q + a["bq"].astype(jnp.float32)
        k = k + a["bk"].astype(jnp.float32)
        v = v + a["bv"].astype(jnp.float32)
    if "q_norm" in a:
        q = rms(q, a["q_norm"], eps)
        k = rms(k, a["k_norm"], eps)
    q, k = rope(q, s["rope_theta"]), rope(k, s["rope_theta"])
    o = attention(q, k, v).reshape(S, H * hd)
    h = h + mm(o, a["wo"].reshape(H * hd, d), quant)
    x = rms(h, lw["ln2"]["scale"], eps)
    m = lw["mlp"]
    f = s["d_ff"]
    c = -(-f // FF_CHUNKS)
    y = jnp.zeros_like(h)
    for f0 in range(0, f, c):
        g = mm(x, m["wg"][:, f0:f0 + c], quant)
        u = mm(x, m["wu"][:, f0:f0 + c], quant)
        y = y + mm(jax.nn.silu(g) * u, m["wd"][f0:f0 + c], quant)
    return h + y


def hidden(s: dict, quant: bool, params, tokens):
    """Final-normed hidden states (S, d) of one sequence of token ids."""
    h = params["embed"][tokens].astype(jnp.float32)

    def body(hh, lw):
        return _layer(s, quant, hh, lw), None
    h, _ = jax.lax.scan(body, h, params["layers"])
    return rms(h, params["final_norm"]["scale"], s["norm_eps"])


def logits(s: dict, quant: bool, params, rows):
    """Logits of hidden-state rows (n, d)."""
    table = params["embed"] if s["tie"] else params["lm_head"]   # (V, d)
    return mm(rows, table.T, quant)


# -- operation and byte counts -------------------------------------------------

def layer_matmul_params(s: dict) -> int:
    """Weights one token multiplies in one layer: q, k, v, o and SwiGLU."""
    d, H, KH, hd, f = (s["d_model"], s["heads"], s["kv_heads"],
                       s["head_dim"], s["d_ff"])
    return d * H * hd + 2 * d * KH * hd + H * hd * d + 3 * d * f


def prompt_flops(s: dict, prompt_len: int) -> float:
    """Forward FLOPs of a P-token prompt: 2 x layer matmul weights per token
    plus causal attention, 4 x H x hd per query-key pair (QK^T and PV), with
    position p (1-based) attending p keys.  No output head: only the last
    position is unembedded, and that is counted with the first output
    token."""
    P = prompt_len
    return (2.0 * s["layers"] * layer_matmul_params(s) * P
            + 4.0 * s["layers"] * s["heads"] * s["head_dim"] * P * (P + 1) / 2)


def head_flops(s: dict) -> float:
    """Output head of one token: 2 x d x V."""
    return 2.0 * s["d_model"] * s["vocab"]


def token_flops(s: dict, keys: int) -> float:
    """Forward FLOPs of one decoded position attending ``keys`` keys,
    output head included."""
    return (2.0 * s["layers"] * layer_matmul_params(s)
            + 4.0 * s["layers"] * s["heads"] * s["head_dim"] * keys
            + head_flops(s))


def attention_calls(s: dict, rows):
    """One group: every layer attends every key.  (FLOPs, bytes) of one
    layer's kernel call over rows of (T new positions, L valid tokens
    including them): query t of a row attends L - T + t + 1 keys; every
    valid key and value is read once, the queries read and the outputs
    written once, all bf16."""
    H, KH, hd = s["heads"], s["kv_heads"], s["head_dim"]
    flops = 0.0
    nbytes = 0.0
    for T, L in rows:
        if T <= 0 or L <= 0:
            continue
        pairs = T * (L - T) + T * (T + 1) / 2
        flops += 4.0 * H * hd * pairs
        nbytes += 2.0 * L * KH * hd * BF16 + 2.0 * T * H * hd * BF16
    return [(s["layers"], flops, nbytes)]
