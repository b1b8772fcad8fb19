"""The one traffic generator: every mix is a data file under
``bench/traffic/`` that this module reads.

A mix file holds:

- ``loop``: ``"open"`` (arrivals at ``rate_per_s``) or ``"closed"``
  (``clients`` that each send their next request when the last one
  completes);
- ``warmup_s``: seconds of this traffic served before the window opens,
  so that caches and queues reach steady state;
- ``drain_s``: how long after the window closes the measured requests
  may take to finish (load keeps being offered meanwhile);
- ``prompt``, ``output``: lognormal lengths in tokens, ``{"median",
  "sigma", "min", "max"}`` (clipped);
- optional ``shared``: long shared prefixes (documents) of lognormal
  length ``shared.tokens``.  A share ``new_share`` of the requests opens
  a new document; every other request re-asks a document that is still
  open, chosen uniformly, with at most ``recent`` open at a time.  Each
  document is asked k = 1 / new_share times, once with each of k
  question lengths; the prompt is the document followed by a
  ``prompt``-length question.

Sizes are stratified in blocks.  A block of n requests holds each length
at the mid-quantiles (i + 0.5) / n of its distribution (documents: n / k
of them, at their own mid-quantiles; questions: the k mid-quantiles).
An open-loop block spans a set time: the warm-up is one block of
``warmup_s``, the window and each block after it one block of the
window's length, so the window holds exactly one whole block.  Its n =
k * round(rate * T / k) arrival gaps are the exponential's mid-quantiles
scaled to sum to its T seconds.  A closed-loop block holds ``clients``
requests, so the clients' first sends are one whole block.

Which output length goes with which prompt is fixed for a block size (a
permutation drawn from the size alone).  The mix's ``order_seed`` draws
the order of the requests and of the gaps within each block, and which
open document a request re-asks; ``--seed`` draws the token ids
(uniform over the vocabulary).  So every seed serves the same requests
at the same times: a window holds a few tens of requests, and the order
among them moves its latencies far more than a change of seed does
otherwise.  The lengths take few distinct values: the program compiles
its KV-hop programs once per page count, and a continuous spread of
lengths would put hundreds of those compiles into a first run's set-up.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, Iterator, List, Tuple

import numpy as np


@dataclass
class Item:
    tokens: np.ndarray        # int32 prompt
    max_new: int              # output tokens requested
    at_s: float               # open loop: due, seconds after traffic starts
    doc: int = -1             # shared-prefix document id, -1 for none


def _lognormal_levels(d: Dict, n: int) -> List[int]:
    nd = NormalDist()
    return [int(min(d["max"], max(d["min"], round(
        d["median"] * math.exp(d["sigma"] * nd.inv_cdf((i + 0.5) / n))))))
        for i in range(n)]


def _paired(d: Dict, n: int) -> List[int]:
    """The n levels of ``d`` in the fixed order that pairs them with a
    block's prompts."""
    levels = _lognormal_levels(d, n)
    return [levels[i] for i in np.random.default_rng([n, 2]).permutation(n)]


class Traffic:
    def __init__(self, spec: Dict, seed: int, vocab: int, window_s: float):
        self.spec = spec
        self.vocab = vocab
        self.window_s = float(window_s)
        self.rng = np.random.default_rng([int(seed), 0])      # token ids
        self.order = np.random.default_rng([int(spec["order_seed"]), 1])
        self.shared = spec.get("shared")
        self.asks = 1
        if self.shared:
            self.asks = round(1.0 / self.shared["new_share"])
            if abs(self.asks * self.shared["new_share"] - 1.0) > 1e-9:
                raise ValueError("shared.new_share must be 1 / a whole "
                                 "number of asks per document")
        self.open = spec["loop"] == "open"
        self._next_doc = 0

    # ---- block sizes ---------------------------------------------------
    def block_size(self, seconds: float) -> int:
        if not self.open:
            return int(self.spec["clients"])
        k = self.asks
        return k * max(1, round(float(self.spec["rate_per_s"]) * seconds
                                / k))

    def _block_sizes(self) -> List[int]:
        """Requests in each kind of block the mix serves."""
        if not self.open:
            return [self.block_size(0.0)]
        return [self.block_size(s)
                for s in (float(self.spec["warmup_s"]), self.window_s)]

    def _docs(self, n: int) -> List[Tuple[int, List[Tuple[int, int]]]]:
        """(document length, [(question length, output length)]) of a
        block of n requests."""
        qs = _lognormal_levels(self.spec["prompt"], self.asks)
        outs = _paired(self.spec["output"], n)
        return [(d, [(q, outs[i * self.asks + j]) for j, q in enumerate(qs)])
                for i, d in enumerate(_lognormal_levels(
                    self.shared["tokens"], n // self.asks))]

    def prompt_lengths(self) -> List[int]:
        """Every prompt length this mix can produce, for any seed."""
        lens = set()
        for n in self._block_sizes():
            if self.shared:
                lens |= {d + q for d, qs in self._docs(n) for q, _ in qs}
            else:
                lens |= set(_lognormal_levels(self.spec["prompt"], n))
        return sorted(lens)

    def max_total_len(self) -> int:
        return max(self.prompt_lengths()) + max(
            max(_lognormal_levels(self.spec["output"], n))
            for n in self._block_sizes())

    # ---- one block -----------------------------------------------------
    def _tokens(self, n: int) -> np.ndarray:
        return self.rng.integers(0, self.vocab, size=n, dtype=np.int32)

    def _perm(self, xs: List) -> List:
        return [xs[i] for i in self.order.permutation(len(xs))]

    def _doc_block(self, n: int) -> Iterator[Tuple[int, np.ndarray, int]]:
        """One block's prompts: each document opened, then re-asked while
        open; the next ask is drawn uniformly over the asks left that may
        come next (a new document only while fewer than ``recent`` are
        open)."""
        docs = self._perm(self._docs(n))
        opened, open_ = 0, []           # open_: [doc id, body, questions]
        for _ in range(n):
            new = (len(docs) - opened) if len(open_) < \
                self.shared["recent"] else 0
            again = sum(len(o[2]) for o in open_)
            r = int(self.order.integers(new + again))
            if r < new:
                d, qs = docs[opened]
                opened += 1
                open_.append([self._next_doc, self._tokens(d),
                              self._perm(qs)])
                self._next_doc += 1
                o = open_[-1]
            else:
                r -= new
                for o in open_:
                    if r < len(o[2]):
                        break
                    r -= len(o[2])
            q, out = o[2].pop()
            if not o[2]:
                open_.remove(o)
            yield o[0], np.concatenate([o[1], self._tokens(q)]), out

    def _block(self, n: int, start: float, seconds: float) -> Iterator[Item]:
        at = [start] * n
        if self.open:
            gaps = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
            scale = seconds / sum(gaps)
            gaps = self._perm([g * scale for g in gaps])
            at = [start + sum(gaps[:i]) for i in range(n)]
        if self.shared:
            reqs = self._doc_block(n)
        else:
            reqs = ((-1, self._tokens(p), o) for p, o in self._perm(list(zip(
                _lognormal_levels(self.spec["prompt"], n),
                _paired(self.spec["output"], n)))))
        for i, (doc, tokens, out) in enumerate(reqs):
            yield Item(tokens, int(out), float(at[i]), doc)

    def items(self) -> Iterator[Item]:
        """Requests in the order they are sent; an open loop's ``at_s``
        puts the window's first request at exactly ``warmup_s``."""
        if not self.open:
            n = self.block_size(0.0)
            while True:
                yield from self._block(n, 0.0, 0.0)
        warm = float(self.spec["warmup_s"])
        if warm > 0:
            yield from self._block(self.block_size(warm), 0.0, warm)
        j = 0
        n = self.block_size(self.window_s)
        while True:
            yield from self._block(n, warm + j * self.window_s,
                                   self.window_s)
            j += 1
