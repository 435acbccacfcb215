"""The one traffic generator: reads a mix's parameters (`traffic/<mix>.json`)
and makes its requests from a seed, at the model's vocabulary.

Arithmetic copied from the program's trace replay (lognormal lengths,
gamma inter-arrivals of shape 1/CV^2), in wall seconds, with one change
that keeps runs comparable: the sizes, their order and the arrival times
are drawn once, by stratified quantiles and permutations of a fixed seed,
and are the same for every seed.  The seed draws the prompt tokens (and,
elsewhere, the weights and the sample that is checked).  Which long
prompt lands in which burst, or which requests finish together, sets the
time to first token and the pace of admission; over the few dozen
requests of a window that pairing, not the server, would decide the
reading (on a TPU v5e, time-to-first-token medians 20 % apart over six
seeds with the order drawn from the seed, against a few percent between
two runs of one seed).  So a cell is a fixed trace of sizes and times,
with fresh tokens and weights on every seed.

Two loops:
* ``backlog``: an offline batch.  Requests are numbered 0, 1, 2, ... and
  drawn on demand; sizes come in blocks of ``block`` requests, each block
  its own fixed permutation of one fixed set.
* ``open``: arrivals on a schedule whatever the server does.  The
  ``round(rate * seconds)`` requests of the measured window arrive inside
  it, their gaps scaled to fill the window; a second such block follows
  the window, as load only, while the window's last requests finish.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np
from scipy import stats

LOOPS = ("backlog", "open")


@dataclasses.dataclass
class Item:
    """One request as the generator makes it."""
    rid: int
    prompt: np.ndarray       # int32 token ids
    max_new: int
    arrival_s: float = 0.0   # seconds after the window opens (open loop)


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """n lognormal lengths (median, sigma) at stratified quantiles, clipped
    to [min, max]; ascending."""
    z = stats.norm.ppf(_quantiles(n))
    x = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def gaps(rate: float, cv: float, n: int) -> np.ndarray:
    """n gamma inter-arrival gaps (mean 1/rate, coefficient of variation
    cv) at stratified quantiles; ascending."""
    shape = 1.0 / cv ** 2
    return stats.gamma.ppf(_quantiles(n), shape, scale=1.0 / (rate * shape))


class Stream:
    """The requests of one mix and one seed."""

    def __init__(self, mix: dict, seed: int, vocab: int, seconds: float):
        if mix["loop"] not in LOOPS:
            raise ValueError(f"unknown loop {mix['loop']!r}; one of {LOOPS}")
        self.mix, self.seed, self.vocab = mix, int(seed), int(vocab)
        self.loop = mix["loop"]
        self._perms = {}
        if self.loop == "open":
            self.block = max(1, round(mix["rate_per_s"] * seconds))
        else:
            self.block = int(mix["block"])
        self._prompt = lengths(mix["prompt_tokens"], self.block)
        self._output = lengths(mix["output_tokens"], self.block)
        self.arrivals: Optional[np.ndarray] = None
        if self.loop == "open":
            g = gaps(mix["rate_per_s"], mix["arrival_cv"], self.block)
            # the window's block fills [0, seconds): its last request comes
            # half a mean gap before the close
            g *= (seconds - 0.5 / mix["rate_per_s"]) / g.sum()
            order = [np.random.default_rng([0, b, 2]).permutation(self.block)
                     for b in (0, 1)]
            self.arrivals = np.concatenate(
                [np.cumsum(g[order[0]]), seconds + np.cumsum(g[order[1]])])
        self.n_window = self.block if self.loop == "open" else None

    def _perm(self, block: int, what: int) -> np.ndarray:
        key = (block, what)
        if key not in self._perms:
            rng = np.random.default_rng([0, block, what])
            self._perms[key] = rng.permutation(self.block)
        return self._perms[key]

    def get(self, rid: int) -> Item:
        """Request ``rid``: its sizes from its block's permutations, its
        prompt tokens uniform over the vocabulary."""
        blk, i = divmod(rid, self.block)
        n_in = int(self._prompt[self._perm(blk, 0)[i]])
        n_out = int(self._output[self._perm(blk, 1)[i]])
        rng = np.random.default_rng([self.seed, rid, 3])
        prompt = rng.integers(0, self.vocab, n_in).astype(np.int32)
        at = float(self.arrivals[rid]) if self.arrivals is not None else 0.0
        return Item(rid=rid, prompt=prompt, max_new=n_out, arrival_s=at)
