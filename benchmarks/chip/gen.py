"""Traffic generators. Each reads the parameters of one traffic file.

``TrainStream`` is the synthetic language-model stream of
``repro.data.pipeline.SyntheticLMDataset``, copied here so that no PR that
changes the program can change the benchmark's input: batch ``i`` is a pure
function of (seed, i), Zipf unigrams with a deterministic bigram chain.

``chat_schedule`` is an open-loop schedule of requests. Every seed gets the
same set of prompt lengths, output lengths, gaps between arrivals and tenant
counts, drawn as evenly spaced quantiles of the stated distributions; the
seed only orders them and picks the prompt tokens. So two seeds offer the
same work in a different order, and the spread between runs is the
system's, not the draw's.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np


def rng_for(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng((int(seed),) + tuple(int(s) for s in salt))


class TrainStream:
    """Batch ``i``: {"tokens", "labels"} int32 [batch, seq]."""

    def __init__(self, *, vocab: int, seq: int, batch: int, seed: int,
                 zipf_a: float = 1.1, structure_p: float = 0.75):
        self.vocab, self.seq, self.batch = vocab, seq, batch
        self.seed, self.structure_p = int(seed), structure_p
        rng = rng_for(seed, 0)
        ranks = rng.permutation(vocab)
        p = 1.0 / np.power(np.arange(1, vocab + 1, dtype=np.float64), zipf_a)
        self._cdf = np.cumsum((p / p.sum())[ranks])
        self._a = int(rng.integers(1, vocab)) | 1      # odd: full cycle
        self._b = int(rng.integers(0, vocab))

    def batch_np(self, step: int) -> dict[str, np.ndarray]:
        rng = rng_for(self.seed, 1, step)
        B, S, V = self.batch, self.seq, self.vocab
        u = rng.random((B, S + 1))
        noise = np.minimum(np.searchsorted(self._cdf, u * self._cdf[-1]),
                           V - 1).astype(np.int64)
        struct = rng.random((B, S)) < self.structure_p
        toks = np.empty((B, S + 1), np.int64)
        toks[:, 0] = noise[:, 0]
        for t in range(1, S + 1):
            succ = (self._a * toks[:, t - 1] + self._b) % V
            toks[:, t] = np.where(struct[:, t - 1], succ, noise[:, t])
        toks = toks.astype(np.int32)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    due_s: float          # seconds after the window opens
    prompt: np.ndarray    # int32 [P]
    max_new_tokens: int
    tenant: int


def _lognormal_quantiles(n: int, median: float, sigma: float, lo: int,
                         hi: int) -> np.ndarray:
    """n evenly spaced quantiles of a lognormal, rounded and clipped."""
    from statistics import NormalDist
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.clip(np.round(median * np.exp(sigma * z)), lo, hi).astype(int)


def _zipf_counts(n: int, k: int, s: float) -> np.ndarray:
    """How many of n requests go to each of k tenants under Zipf(s),
    largest remainders rounding."""
    w = 1.0 / np.arange(1, k + 1) ** s
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - counts))[:n - counts.sum()]:
        counts[i] += 1
    return counts


def chat_schedule(mix: dict, *, seed: int, seconds: float,
                  vocab: int) -> list[Request]:
    """round(rate · seconds) requests due in [0, seconds): Poisson gaps
    (exponential quantiles, scaled to fill the window), lognormal prompt
    and output lengths, tenants by Zipf popularity; ordered by the seed."""
    n = max(1, int(round(mix["rate"] * seconds)))
    p, o = mix["prompt"], mix["output"]
    prompts = _lognormal_quantiles(n, p["median"], p["sigma"], p["min"],
                                   p["max"])
    outputs = _lognormal_quantiles(n, o["median"], o["sigma"], o["min"],
                                   o["max"])
    gaps = -np.log(1.0 - (np.arange(n) + 0.5) / n)
    gaps *= seconds / gaps.sum()
    tenants = np.repeat(np.arange(mix["tenants"]),
                        _zipf_counts(n, mix["tenants"], mix["zipf_s"]))
    rng = rng_for(seed, 2, 0)
    prompts, outputs, gaps, tenants = (rng.permutation(a) for a in
                                       (prompts, outputs, gaps, tenants))
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return [Request(i, float(due[i]),
                    rng.integers(0, vocab, int(prompts[i]), dtype=np.int32),
                    int(outputs[i]), int(tenants[i])) for i in range(n)]


def percentile(values, q: float) -> float:
    """The q-th percentile (0-100), nearest rank: the smallest value with
    at least q% of the sample at or below it."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        raise ValueError("percentile of an empty sample")
    k = max(0, math.ceil(q / 100.0 * v.size) - 1)
    return float(v[k])
