"""Operations and bytes the algorithm needs, computed from shapes.

These are the benchmark's own counts: what serving a token or a kernel call
requires, not what the program happens to execute (padding, pages visited
beyond a sequence's length and rejected draft work do not count).  Every
count is of one model spec from ``harness.models``."""
from __future__ import annotations

from typing import Iterable, Tuple

BF16 = 2  # bytes per element of weights, activations and KV


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


def attention_call(s: dict, rows: Iterable[Tuple[int, int]]
                   ) -> Tuple[float, float]:
    """(FLOPs, bytes) of ONE paged-attention kernel call (one layer) over
    rows of (T new positions, L valid tokens including them).  Query t of a
    row attends L - T + t + 1 keys.  Bytes: every valid key and value read
    once, the queries read and the outputs written once, all bf16."""
    H, KH, hd = s["heads"], s["kv_heads"], s["head_dim"]
    flops = 0.0
    nbytes = 0.0
    for T, L in rows:
        if T <= 0 or L <= 0:
            continue
        pairs = T * (L - T) + T * (T + 1) / 2
        flops += 4.0 * H * hd * pairs
        nbytes += 2.0 * L * KH * hd * BF16 + 2.0 * T * H * hd * BF16
    return flops, nbytes


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
