"""Token batches from a traffic file and the run's seed.

The one generator every training cell reads.  Batch ``k`` is a pure
function of (seed, k), so the reference sees the very rows the program was
fed, and every step of a run gets rows of its own.  The program receives
only the generated tokens, through its own feed (``StepSetup.side_at``).

``successor_chain``: each row starts at a uniform token and continues with
token + 1, except that a share ``jump_share`` of positions jumps to a fresh
uniform token (the arithmetic of the program's ``SyntheticLM``, vectorised).
Every id of the vocabulary can occur, so the embedding's scatter and the
head's full width do real work.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

_SEED_MASK = (1 << 64) - 1


def seed_words(seed: int, n: int = 2) -> np.ndarray:
    """``n`` uint32 words drawn from ``seed``: a key for any whole number."""
    return np.random.SeedSequence(seed & _SEED_MASK).generate_state(n, np.uint32)


class TokenBatches:
    """``batch_at(k)`` -> {tokens, labels}: (microbatches * microbatch_size, seq)."""

    def __init__(self, traffic: Dict[str, Any], vocab: int, seed: int):
        kind = traffic["tokens"]["kind"]
        if kind != "successor_chain":
            raise ValueError(f"unknown token kind {kind!r}")
        self.rows = traffic["microbatches"] * traffic["microbatch_size"]
        self.seq = traffic["seq_len"]
        self.jump_share = float(traffic["tokens"]["jump_share"])
        self.vocab = vocab
        self.seed = seed & _SEED_MASK

    def batch_at(self, step: int) -> Dict[str, np.ndarray]:
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, step]))
        n, s = self.rows, self.seq + 1
        values = rng.integers(0, self.vocab, size=(n, s))
        anchor = rng.random((n, s)) < self.jump_share
        anchor[:, 0] = True
        pos = np.arange(s)
        last = np.maximum.accumulate(np.where(anchor, pos, 0), axis=1)
        base = np.take_along_axis(values, last, axis=1)
        toks = ((base + (pos - last)) % self.vocab).astype(np.int32)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
