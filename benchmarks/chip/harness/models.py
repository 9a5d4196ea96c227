"""A configuration file -> the two model specs the harness works with.

A spec is a plain dict of the sizes the weight generator, the reference and
the operation counts need, read from the configuration's own
(Hugging Face style) keys.  Nothing here imports the program."""
from __future__ import annotations

import json
from pathlib import Path

CHIP_DIR = Path(__file__).resolve().parents[1]


def _spec(name: str, c: dict) -> dict:
    if c.get("hidden_act", "silu") != "silu":
        raise ValueError(f"{name}: only SwiGLU (silu) models are supported")
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


def load_config(name: str, root: Path = CHIP_DIR) -> dict:
    """configs/<name>.json -> {"name", "file", "target": spec, "draft": spec,
    "registered_as"}."""
    path = root / "configs" / f"{name}.json"
    raw = json.loads(path.read_text())
    if raw["name"] != name:
        raise ValueError(f"{path} names itself {raw['name']!r}")
    return {"name": name, "file": str(path),
            "registered_as": raw.get("registered_as"),
            "logit_gap_limit": raw.get("logit_gap_limit"),
            "target": _spec(name, raw),
            "draft": _spec(raw["draft"]["name"], raw["draft"])}


def rehearsal_spec(spec: dict) -> dict:
    """The CPU rehearsal's cut of a spec: the repository's reduced preset
    (2 layers, hidden 128, 4 heads of 32, SwiGLU 256, vocabulary 256), kept
    with the benchmark so that the rehearsal's weights are its own."""
    out = dict(spec, layers=min(spec["layers"], 2), d_model=128, heads=4,
               d_ff=256, vocab=256, head_dim=32)
    out["kv_heads"] = (1 if spec["kv_heads"] == 1 else
                       2 if spec["kv_heads"] < spec["heads"] else 4)
    return out
