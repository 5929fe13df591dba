"""Closed-loop traffic from a mix file and a seed.

A mix file (``traffic/<mix>.json``) gives the number of clients and the
prompt and output length distributions: lognormal with a median and a
``sigma`` (of the log), rounded and clipped to ``[min, max]``.

Every seed serves the same lengths, so the seed changes the tokens but
not the work.  There is one stream of lengths per client: the ``i``-th
request of stream ``k`` takes its lengths at the quantiles of a
low-discrepancy sequence (radical inverses in base 2 for the output,
base 3 for the prompt), shifted by offsets fixed per stream, so every
prefix of a stream is spread over the whole distribution.  A stream's
first request has its output budget drawn from the residual of the
output distribution (the remaining life of a request found in
progress), at quantiles spread evenly over the streams, so completions
do not bunch when the window opens.  Client ``k`` always takes stream
``k``: dealt in another order per seed, the streams put another number
of prefill chunks into the window, which moved a closed loop of 8
clients by 9% from seed to seed.  The seed draws the prompt token ids,
uniform over the vocabulary, from ``(seed, client, index)``.
"""
from __future__ import annotations

import dataclasses
import json
import math
from statistics import NormalDist
from typing import List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Lengths:
    median: float
    sigma: float
    min: int
    max: int

    def at(self, u: float) -> int:
        """The clipped lognormal's length at quantile ``u`` in (0, 1)."""
        u = min(max(u, 1e-9), 1 - 1e-9)
        x = math.exp(math.log(self.median) + self.sigma * NormalDist().inv_cdf(u))
        return int(min(max(round(x), self.min), self.max))

    def pmf(self) -> np.ndarray:
        """P(length == L) for L in 0..max, by quantiles of a fine grid."""
        grid = (np.arange(20000) + 0.5) / 20000
        counts = np.bincount([self.at(u) for u in grid], minlength=self.max + 1)
        return counts / counts.sum()

    def residual_at(self, u: float) -> int:
        """Remaining life at quantile ``u``: P(r == j) ~ P(L >= j), j >= 1."""
        surv = np.cumsum(self.pmf()[::-1])[::-1]  # P(L >= j) for j = 0..max
        cdf = np.cumsum(surv[1:]) / surv[1:].sum()
        return int(np.searchsorted(cdf, u, side="right")) + 1


@dataclasses.dataclass(frozen=True)
class Mix:
    name: str
    clients: int
    prompt: Lengths
    output: Lengths


@dataclasses.dataclass(frozen=True)
class Request:
    client: int
    index: int
    prompt: List[int]
    max_new_tokens: int


def load_mix(path) -> Mix:
    with open(path) as f:
        raw = json.load(f)
    return Mix(
        name=raw["name"],
        clients=int(raw["clients"]),
        prompt=Lengths(**raw["prompt"]),
        output=Lengths(**raw["output"]),
    )


def radical_inverse(i: int, base: int) -> float:
    out, f = 0.0, 1.0 / base
    while i:
        i, digit = divmod(i, base)
        out += digit * f
        f /= base
    return out


class Traffic:
    """The requests of every client, made on demand from the seed."""

    def __init__(self, mix: Mix, seed: int, vocab: int):
        self.mix, self.seed, self.vocab = mix, seed, vocab
        n = mix.clients
        streams = np.arange(n)
        # fixed per stream: (prompt, output) quantile shifts, first residual
        self._offsets = np.stack([(streams + 0.5) / n, (streams * 0.618034) % 1.0], 1)
        self._first = (streams + 0.5) / n
        self._residual = {}

    def lengths(self, client: int, index: int):
        op, oo = self._offsets[client]
        plen = self.mix.prompt.at((radical_inverse(index + 1, 3) + op) % 1.0)
        if index == 0:
            u = float(self._first[client])
            if u not in self._residual:
                self._residual[u] = self.mix.output.residual_at(u)
            return plen, self._residual[u]
        return plen, self.mix.output.at((radical_inverse(index, 2) + oo) % 1.0)

    def request(self, client: int, index: int) -> Request:
        plen, new = self.lengths(client, index)
        rng = np.random.default_rng([self.seed, 1, client, index])
        prompt = rng.integers(0, self.vocab, plen).tolist()
        return Request(client, index, prompt, new)

    def chunk_widths(self, block: int, chunk: int) -> List[int]:
        """Every prefill chunk width this mix's prompts can produce."""
        widths = set()
        for plen in range(self.mix.prompt.min, self.mix.prompt.max + 1):
            rest = plen
            while rest > chunk:
                widths.add(chunk)
                rest -= chunk
            widths.add(-(-rest // block) * block)
        return sorted(widths)
