"""The paper's own experimental pair (DeepSeek-R1-Distill-Qwen-7B target +
DeepSeek-R1-DRAFT-Qwen2.5-0.5B draft) — qwen2-7b architecture.

Used by the faithful-reproduction benchmarks (Tables 3/5/6, Figures 2/9-15).

``ONE_CHIP`` is the pair's cut for one TPU v5e (16 GB HBM), registered as
arch ``paper-7b-1chip``:

* source: the Qwen2-7B layout of the target (``config.json`` of
  Qwen/Qwen2-7B: 28 layers, hidden 3584, 28 query / 4 KV heads of 128,
  SwiGLU 18944, vocabulary 152064, qkv bias) and the Qwen2.5-0.5B layout of
  the draft (24 layers, hidden 896, 14 / 2 heads of 64, SwiGLU 4864, tied
  embeddings);
* key changed: the target's ``num_layers`` 28 -> 14.  Whole it is 7.6 B
  parameters, 15.2 GB in bf16, and cannot sit on one 16 GB chip beside its
  draft and a KV cache;
* deployment it stands for: one stage of a two-stage pipeline, the other 14
  layers on a second chip; this chip holds its 14 layers, the embedding and
  the output head, and the whole 24-layer draft.  Every width, head count,
  the vocabulary and ``qkv_bias`` are as published;
* sizes: about 8.7 GB of target and 0.99 GB of draft weights in bf16; at
  block size 16 the KV cache costs 28 KiB per token in the target pool and
  12 KiB in the draft pool.

The cut has its own ``name``: ``RooflineCostModel`` caches parameter counts
by name, and a shared name would size the pool from the uncut target.
"""
from .base import ModelConfig

CONFIG = ModelConfig(
    name="paper-7b",
    family="dense",
    num_layers=28,
    d_model=3584,
    num_heads=28,
    num_kv_heads=4,
    d_ff=18944,
    vocab_size=152064,
    mlp_type="swiglu",
    qkv_bias=True,
    rope_theta=10_000.0,
    tie_embeddings=False,
)

DRAFT = ModelConfig(
    name="paper-7b-draft",      # qwen2.5-0.5B layout
    family="dense",
    num_layers=24,
    d_model=896,
    num_heads=14,
    num_kv_heads=2,
    d_ff=4864,
    vocab_size=152064,
    qkv_bias=True,
    tie_embeddings=True,
)

ONE_CHIP = CONFIG.replace(name="paper-7b-1chip", num_layers=14)
