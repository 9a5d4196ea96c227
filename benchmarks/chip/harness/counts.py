"""Operations and bytes the algorithm needs, computed from shapes.

These are the benchmark's own counts: what serving a token or a kernel call
requires, not what the program happens to execute (padding, pages visited
beyond a sequence's length and rejected draft work do not count).  Every
count is of one model spec from ``harness.models``, by the spec's
architecture module."""
from __future__ import annotations

from typing import Iterable, List, Tuple

from .models import arch

BF16 = 2  # bytes per element of weights, activations and KV


def layer_matmul_params(s: dict) -> int:
    """Weights one token multiplies in one layer."""
    return arch(s).layer_matmul_params(s)


def prompt_flops(s: dict, prompt_len: int) -> float:
    """Forward FLOPs of a prompt, without the output head (the last
    position's is counted with the first output token)."""
    return arch(s).prompt_flops(s, prompt_len)


def head_flops(s: dict) -> float:
    """Output head of one token."""
    return arch(s).head_flops(s)


def token_flops(s: dict, keys: int) -> float:
    """Forward FLOPs of one decoded position attending ``keys`` keys,
    output head included."""
    return arch(s).token_flops(s, keys)


def attention_calls(s: dict, rows: Iterable[Tuple[int, int]]
                    ) -> List[Tuple[int, float, float]]:
    """[(layers, FLOPs, bytes)]: for each kind of layer, how many layers
    of it the model has and the operations and bytes of ONE of their
    paged-attention kernel calls over rows of (T new positions, L valid
    tokens including them), valid keys only."""
    return arch(s).attention_calls(s, list(rows))


def attention_call(s: dict, rows: Iterable[Tuple[int, int]]
                   ) -> Tuple[float, float]:
    """(FLOPs, bytes) of ONE kernel call of a model whose layers all
    attend alike."""
    groups = attention_calls(s, rows)
    if len(groups) != 1:
        raise ValueError(f"{s['name']}: its layers attend in "
                         f"{len(groups)} ways; use attention_calls")
    return groups[0][1:]


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
