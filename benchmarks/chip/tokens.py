"""The benchmark's token stream: one general generator, parameters from a
traffic file.

A copy of the arithmetic of ``repro.data.SyntheticLMData``: every row of
every step is its own counter-based Philox stream keyed by the seed, starts
at a Zipf-drawn token and walks a fixed Markov pattern table, with a share
of uniformly random tokens mixed in. The copy keeps the token stream and its
host cost the yardstick's own, so a change to the program's data module
cannot change what is measured.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

import numpy as np
from jax.profiler import TraceAnnotation


class TokenStream:
    """``batch_at(step)`` -> {"tokens", "labels"} (batch, seq) int32; a pure
    function of (seed, step). Each call's host time is recorded."""

    def __init__(self, vocab: int, seq: int, batch: int, seed: int, *,
                 zipf_a: float, n_patterns: int, noise: float):
        self.vocab, self.seq, self.batch, self.seed = vocab, seq, batch, seed
        self.noise = noise
        rng = np.random.default_rng(seed ^ 0x5EED)
        self._mult = rng.integers(1, vocab, n_patterns)
        self._add = rng.integers(0, vocab, n_patterns)
        self._zipf_a = zipf_a
        self.position = 0
        self.calls: List[float] = []    # host seconds of each batch_at

    @classmethod
    def from_traffic(cls, traffic: dict, vocab: int, seq: int, batch: int,
                     seed: int) -> "TokenStream":
        t = traffic["tokens"]
        return cls(vocab, seq, batch, seed, zipf_a=t["zipf_a"],
                   n_patterns=t["n_patterns"], noise=t["noise"])

    def _gen(self, step: int, rows: np.ndarray) -> Dict[str, np.ndarray]:
        streams = [np.random.Generator(np.random.Philox(
            key=self.seed, counter=[0, int(r), step, 1])) for r in rows]
        pat = np.array([s.integers(0, len(self._mult)) for s in streams])
        start = np.array([s.zipf(self._zipf_a) % self.vocab for s in streams])
        noise = np.stack([s.random(self.seq) for s in streams])
        rand_tok = np.stack([s.integers(0, self.vocab, self.seq)
                             for s in streams])
        toks = np.empty((len(rows), self.seq + 1), np.int32)
        toks[:, 0] = start
        cur = start.astype(np.int64)
        mult, add = self._mult[pat], self._add[pat]
        for t in range(self.seq):
            cur = (cur * mult + add) % self.vocab
            nxt = np.where(noise[:, t] < self.noise, rand_tok[:, t], cur)
            toks[:, t + 1] = nxt
            cur = nxt.astype(np.int64)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

    def batch_at(self, step: int) -> Dict[str, np.ndarray]:
        t0 = time.perf_counter()
        with TraceAnnotation("bench.input"):     # a host span in the trace
            out = self._gen(step, np.arange(self.batch))
        self.calls.append(time.perf_counter() - t0)
        return out

    def restore(self, position: int) -> None:
        """Set the stream's position, as a resumed job does."""
        self.position = int(position)

    def rows(self, steps, n_rows: Optional[int] = None):
        """The batches of ``steps`` without timing (for the reference)."""
        rows = np.arange(n_rows if n_rows is not None else self.batch)
        return [self._gen(s, rows) for s in steps]
