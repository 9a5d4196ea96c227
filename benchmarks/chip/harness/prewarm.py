"""Set-up's first phase: every step program the cell's window can call, run
once before the stream starts, so that none is compiled (or loaded from the
persistent cache) inside the window.

``RealBackend`` keys its step programs by padded shapes: the batch, the new
tokens of an extension and the block-table width, each rounded up to a power
of two.  The mix's ``prewarm`` entry names the decode batches and table
widths its window reaches and the prefill batches and draft catch-up lengths
it can take; the prompt classes come from the mix's own set of prompt
lengths.  Every program runs through the backend's own entry points
(``prefill``, ``step``, ``draft_catchup``) on synthetic sequences with
negative request ids.  Decode and catch-up batches need sequences of a
given length without prefilling them, so their per-sequence lengths are
set in the backend's runtime state (``tkv.ctx``, ``dkv.ctx``, the last
token) over blocks allocated from the pool.  Everything is released
afterwards; what the programs wrote lands in blocks that every later owner
overwrites before it reads them."""
from __future__ import annotations

import itertools
from typing import Iterable, List


def pow2(n: int) -> int:
    """Next power of two, as the backend pads."""
    return 1 << max(n - 1, 0).bit_length()


def prefill_classes(prompt_lengths: Iterable[int], block_size: int) -> List[int]:
    """One prompt length for each (padded length, table width) that a
    prefill of these prompts can take: both follow from the longest prompt
    of the call, so one length per class covers every batch of them."""
    out = {}
    for p in sorted(set(prompt_lengths)):
        out.setdefault((pow2(p), pow2(-(-(p + 1) // block_size))), p)
    return sorted(out.values())


def run(engine, pw: dict, prompt_lengths: Iterable[int],
        gamma_max: int) -> dict:
    """Run the programs ``pw`` names; returns how many calls of each kind."""
    from repro.serving.request import Request, Sequence
    be, bm = engine.backend, engine.scheduler.bm
    bs = bm.block_size
    ids = itertools.count(-1, -1)

    def make(n_tokens: int) -> Sequence:
        return Sequence(Request(next(ids), 0.0, n_tokens, 1,
                                prompt_tokens=[0] * n_tokens))

    def seated(seqs: List[Sequence]) -> None:
        for s in seqs:
            c = s.request.prompt_len
            bm.allocate(s.req_id, c + 1)
            be.tkv.ctx[s.req_id] = be.dkv.ctx[s.req_id] = c
            be.last_token[s.req_id] = 0
            be.tokens_out[s.req_id] = [0]

    def release(seqs: List[Sequence]) -> None:
        for s in seqs:
            be.release(s)
            be.tokens_out.pop(s.req_id, None)

    counts = {"prefill": 0, "step": 0, "draft_catchup": 0}
    for p in prefill_classes(prompt_lengths, bs):
        for b in pw["prefill_batches"]:
            seqs = [make(p) for _ in range(b)]
            be.prefill(seqs, with_draft=True)
            release(seqs)
            counts["prefill"] += 1
    # the widest speculative step writes gamma_max + 1 positions, and every
    # step of the sweep below may keep them all
    grow = (gamma_max + 1) * (gamma_max + 2) // 2
    for b in pw["decode_batches"]:
        for w in pw["widths"]:
            # one sequence fills more than half of width w and stays inside
            # it through every step below; the rest hold one block each
            long_ctx = max(w // 2 * bs + 1, 1)
            if -(-(long_ctx + grow + gamma_max + 1) // bs) > w:
                raise ValueError(f"width {w} is too narrow to pre-warm")
            seqs = [make(long_ctx)] + [make(1) for _ in range(b - 1)]
            seated(seqs)
            for g in range(gamma_max + 1):
                be.step(seqs, g)
                counts["step"] += 1
            for d in pw["catchup_lengths"]:
                for s in seqs:
                    be.dkv.ctx[s.req_id] = max(be.tkv.ctx[s.req_id] - d, 0)
                be.draft_catchup(seqs)
                counts["draft_catchup"] += 1
            release(seqs)
    return counts
