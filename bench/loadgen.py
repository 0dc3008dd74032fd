"""The one generator of every traffic mix: reads a mix's parameters from
its data file (``bench/traffic/<name>.json``) and makes its inputs from
``--seed``.

Every seed gets the same work in another order: the multiset of prompt
lengths and output budgets is fixed by the mix (exact shares of the
length menu, quantiles of the output distribution), and so is the
arrival schedule (quantiles of the gap distribution in one fixed order);
the seed permutes the requests over it and draws the token ids.
So two seeds differ in order and content, never in how much there is to
do, and a run's spread is the system's, not the dice's.

Mix keys:

* ``mode``: ``train`` or ``serve``;
* train: ``batch``, ``seq_len`` — rows of a seeded bigram token stream
  (the program's ``SyntheticLM`` chart, copied), every row different;
* serve: ``loop`` (``open`` Poisson arrivals at ``rate_per_s``, or
  ``closed`` with ``clients`` each keeping one request in flight),
  ``prompt_lens`` with ``prompt_weights``, ``output`` (``median``,
  ``sigma`` of a log-normal, clipped to ``min``..``max``), ``slots``,
  ``max_len``.
"""
from __future__ import annotations

import dataclasses
import statistics
from typing import List

import numpy as np


def rng(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one stream of one seed (any non-negative int)."""
    return np.random.default_rng([int(seed), *stream])


# ---------------------------------------------------------------------------
# training rows
# ---------------------------------------------------------------------------

class BigramRows:
    """Seeded rows of a learnable token stream: each token follows one of
    8 successors of the previous one 85% of the time (Zipf-weighted first
    tokens), otherwise a uniform draw.  Rows of ``batch(step)`` are a pure
    function of ``(seed, step)``."""

    def __init__(self, vocab: int, batch: int, seq_len: int, seed: int):
        self.vocab, self.B, self.S, self.seed = vocab, batch, seq_len, seed
        g = rng(seed, 0)
        self.nexts = g.integers(0, vocab, (vocab, 8), dtype=np.int32)
        zipf = 1.0 / np.arange(1, vocab + 1)
        self.marginal = zipf / zipf.sum()

    def batch(self, step: int) -> dict:
        g = rng(self.seed, 1, step)
        B, S = self.B, self.S
        toks = np.empty((B, S + 1), np.int32)
        toks[:, 0] = g.choice(self.vocab, size=B, p=self.marginal)
        follows = g.random((B, S)) < 0.85
        pick = g.integers(0, 8, (B, S))
        resample = g.integers(0, self.vocab, (B, S), dtype=np.int32)
        for t in range(S):
            nxt = self.nexts[toks[:, t], pick[:, t]]
            toks[:, t + 1] = np.where(follows[:, t], nxt, resample[:, t])
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


# ---------------------------------------------------------------------------
# serving requests
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Request:
    index: int
    due_s: float          # offset from the window's start (open loop)
    prompt: np.ndarray    # int32 token ids
    max_new: int
    client: int = 0


_NORMAL = statistics.NormalDist()


def _shares(weights, n: int) -> List[int]:
    """Split ``n`` into counts proportional to ``weights`` (largest
    remainder), so every seed gets exactly the same menu counts."""
    w = np.asarray(weights, np.float64)
    raw = w / w.sum() * n
    counts = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - counts))[:n - counts.sum()]:
        counts[i] += 1
    return counts.tolist()


def sizes(mix: dict, seed: int, n: int, stream: int = 0):
    """``n`` (prompt_len, max_new) pairs: exact menu shares and output
    quantiles, paired the same way for every seed (a fixed shuffle), the
    pairs then put in the seed's order."""
    lens = np.repeat(mix["prompt_lens"],
                     _shares(mix["prompt_weights"], n))
    o = mix["output"]
    z = np.array([_NORMAL.inv_cdf((i + 0.5) / n) for i in range(n)])
    outs = np.clip(np.round(o["median"] * np.exp(o["sigma"] * z)),
                   o["min"], o["max"]).astype(int)
    pairs = list(zip(lens.tolist(), rng(0, 2).permutation(outs).tolist()))
    order = rng(seed, 2, stream).permutation(n)
    return [pairs[i] for i in order]


def prompts(mix: dict, seed: int, lens, stream: int = 0):
    g = rng(seed, 3, stream)
    return [g.integers(0, mix["vocab_ids"], L, dtype=np.int32)
            for L in lens]


def open_loop(mix: dict, seed: int, seconds: float) -> List[Request]:
    """Poisson arrivals at ``rate_per_s`` over ``seconds``: the gaps are
    the exponential distribution's quantiles in one fixed shuffled order,
    the mix's own arrival schedule, the same for every seed (a seed that
    reordered the bursts moved the p90 of time to first token by some 7%
    from run to run on a TPU v5e); the seed draws which request arrives
    when."""
    n = max(1, int(round(mix["rate_per_s"] * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / mix["rate_per_s"]
    gaps = rng(0, 4).permutation(gaps)
    due = np.cumsum(gaps) - gaps[0]
    sz = sizes(mix, seed, n)
    ps = prompts(mix, seed, [L for L, _ in sz])
    return [Request(i, float(due[i]), ps[i], int(sz[i][1]))
            for i in range(n)]


def closed_loop(mix: dict, seed: int, per_client: int) -> List[List[Request]]:
    """``clients`` queues of ``per_client`` requests each; a client sends
    its next request when its previous one finishes."""
    out = []
    for c in range(mix["clients"]):
        sz = sizes(mix, seed, per_client, stream=1 + c)
        ps = prompts(mix, seed, [L for L, _ in sz], stream=1 + c)
        out.append([Request(i, 0.0, ps[i], int(sz[i][1]), client=c)
                    for i in range(per_client)])
    return out


def warmup_requests(mix: dict) -> List[Request]:
    """One short request of each prompt length the mix uses (token ids
    fixed, not seeded: warm-up compiles shapes, it measures nothing)."""
    return [Request(i, 0.0, np.arange(L, dtype=np.int32) % mix["vocab_ids"],
                    2) for i, L in enumerate(mix["prompt_lens"])]
