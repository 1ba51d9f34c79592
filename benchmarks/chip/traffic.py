"""The general generator: turns a traffic file and ``--seed`` into the
training job's batches.

A traffic file (``traffic/<name>.json``) holds the job's parameters:
``global_batch``, ``seq_len``, the token distribution (``tokens``), the
optimizer and objective the job states, and, for a pipelined job, the
islands and options the planner searches (``plan``).  Tokens follow a
Zipf law over the configuration's vocabulary, p(rank r) ~ r^-exponent, as
text does; each step's batch is a pure function of (seed, step), and its
rows all differ.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class FeedState:
    seed: int
    step: int

    def to_dict(self):
        return {"seed": self.seed, "step": self.step}


class Feed:
    """Batches for the Trainer: ``batch_at(step)`` and a ``state``."""

    def __init__(self, traffic: dict, vocab: int, seed: int):
        self.batch = int(traffic["global_batch"])
        self.seq = int(traffic["seq_len"])
        self.state = FeedState(int(seed), 0)
        r = np.arange(1, vocab + 1, dtype=np.float64)
        p = r ** -float(traffic["tokens"]["zipf_exponent"])
        self._cdf = np.cumsum(p / p.sum())
        self._vocab = vocab

    def batch_at(self, step: int) -> dict:
        rng = np.random.default_rng([self.state.seed, int(step)])
        u = rng.random((self.batch, self.seq + 1))
        toks = np.minimum(np.searchsorted(self._cdf, u, side="right"),
                          self._vocab - 1).astype(np.int32)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
