"""The one traffic generator: reads a mix's parameters from
``traffic/<name>.json`` and turns them and ``--seed`` into requests.

Lengths follow the mix's laws, drawn *stratified*: each block of ``block``
consecutive requests holds the same set of prompt lengths and output
lengths (the laws' quantiles at the block's midpoints), and the seed only
permutes them within the block.  Every seed therefore offers
the same work in another order, so runs with different seeds differ by
order alone.  Token ids are uniform over the vocabulary, drawn per request
from (seed, request index).

Laws:
  lognormal  {"mu", "sigma", "min", "max"}: exp(mu + sigma * z), clipped

Arrivals: a closed loop of ``clients``, each sending its next request as
soon as its previous one finishes.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist
from typing import List

import numpy as np

from .models import CHIP_DIR

_Z = NormalDist()


def load_traffic(name: str, root: Path = CHIP_DIR) -> dict:
    path = root / "traffic" / f"{name}.json"
    mix = json.loads(path.read_text())
    if mix["name"] != name:
        raise ValueError(f"{path} names itself {mix['name']!r}")
    if mix["loop"] != "closed":
        raise ValueError(f"{path}: loop must be 'closed'")
    return mix


def rehearsal_cut(mix: dict) -> dict:
    """The CPU rehearsal's cut of a mix: short prompts and outputs, few
    clients, a small batch, seconds of warm-up (reduced widths,
    interpret-mode kernels).  Never measured."""
    out = json.loads(json.dumps(mix))
    out["prompt"].update(min=4, max=32)
    out["output"].update(min=4, max=8)
    out["engine"]["max_batch"] = 4
    # twice the batch, as in the cells: a queue as long as the batch
    out["clients"] = 2 * out["engine"]["max_batch"]
    out["ramp_per_step"] = 2
    out["warmup"] = {"steps": 6}
    out["prewarm"] = {"decode_batches": [4], "widths": [4],
                      "prefill_batches": [1, 2, 4],
                      "catchup_lengths": [1, 2, 4, 8, 16]}
    return out


def _quantile(law: dict, u: float) -> float:
    if law["law"] == "lognormal":
        return math.exp(law["mu"] + law["sigma"] * _Z.inv_cdf(u))
    raise ValueError(f"unknown law {law['law']!r}")


@dataclass
class Req:
    index: int
    prompt_len: int
    output_len: int


class Stream:
    """Request i of the mix under ``seed``; requests are made on demand."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix = mix
        self.seed = int(seed) % (1 << 64)
        self.vocab = vocab
        self.block = int(mix["block"])
        mids = [(j + 0.5) / self.block for j in range(self.block)]
        p, o = mix["prompt"], mix["output"]
        self._prompts = [int(min(max(_quantile(p, u), p["min"]), p["max"]))
                         for u in mids]
        self._outputs = [int(min(max(_quantile(o, u), o["min"]), o["max"]))
                         for u in mids]
        self._perm: dict = {}

    @property
    def prompt_lengths(self) -> List[int]:
        """Every prompt length the stream can send."""
        return sorted(set(self._prompts))

    def _perms(self, b: int):
        if b not in self._perm:
            rng = np.random.default_rng([self.seed, b])
            self._perm[b] = tuple(rng.permutation(self.block)
                                  for _ in range(2))
        return self._perm[b]

    def request(self, i: int) -> Req:
        b, j = divmod(i, self.block)
        pp, po = self._perms(b)
        return Req(i, self._prompts[pp[j]], self._outputs[po[j]])

    def tokens(self, i: int, n: int) -> List[int]:
        rng = np.random.default_rng([self.seed, 1 << 20, i])
        return rng.integers(0, self.vocab, n).tolist()
