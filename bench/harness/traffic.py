"""The one traffic generator: every mix is a data file of parameters.

A mix file (``bench/traffic/<name>.json``) has ``"mode"``:

* ``"serve"``: requests with prompt and output lengths drawn from
  clipped log-normal distributions, optionally behind shared prefixes
  picked with Zipf popularity, arriving open-loop at ``rate_per_s``
  (Poisson) or kept ``clients`` deep in a closed loop.  Optional:
  ``"bursts": {"on_s", "off_s"}`` makes the open loop's arrivals come
  only in on-periods, at ``rate_per_s`` while on; ``"classes"``, a list
  of ``{"weight", "prompt_tokens", "output_tokens"}``, mixes request
  kinds (short chat beside long documents) in one queue, each class's
  prompt and output lengths paired, in shares by ``weight``;
* ``"train"``: ``batch`` x ``seq`` token batches.

Every seed serves the same set of sizes and inter-arrival gaps, in an
order the seed draws: the seed changes the order and the token ids,
never the amount of work.  The order is drawn in blocks of
``order_block`` (16) requests, each block holding one value from each
of 16 equal strata of the sorted set, so that every stretch of the run
carries the same mix of long and short requests and gaps; a plain
shuffle let one seed bunch the long requests and the short gaps and
moved the tail of time to first token fivefold between seeds.  Token
ids are uniform over the whole vocabulary.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Any, Dict, List

import numpy as np

# distinct streams under one seed
_PREFIX, _ORDER, _GAPS, _ASSIGN, _PROMPT, _BATCH = range(6)


def _rng(seed: int, *path: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 64), *path])


def quantile_sizes(dist: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` integer sizes at the mid-quantiles ``(i + 0.5) / n`` of a
    log-normal (``median``, ``sigma``) clipped to [``min``, ``max``]:
    the same set for every seed."""
    normal = statistics.NormalDist()
    z = np.array([normal.inv_cdf((i + 0.5) / n) for i in range(n)])
    vals = dist["median"] * np.exp(dist["sigma"] * z)
    return np.clip(np.rint(vals), dist["min"], dist["max"]).astype(np.int64)


def block_order(values: np.ndarray, block: int,
                rng: np.random.Generator) -> np.ndarray:
    """``values`` in an order drawn by ``rng`` where each run of
    ``block`` consecutive entries holds one value from each of ``block``
    equal strata of the sorted values (the remainder goes last)."""
    v = np.sort(np.asarray(values))
    nb = len(v) // block
    if nb == 0:
        return rng.permutation(v)
    strata = v[: nb * block].reshape(block, nb)
    strata = np.stack([rng.permutation(row) for row in strata])
    blocks = np.stack([rng.permutation(col) for col in strata.T])
    return np.concatenate([blocks.reshape(-1),
                           rng.permutation(v[nb * block:])])


def exp_gaps(rate: float, n: int) -> np.ndarray:
    """``n`` inter-arrival gaps at the mid-quantiles of an exponential
    of mean ``1 / rate``."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def shares(weights, n: int) -> np.ndarray:
    """``n`` split in proportion to ``weights`` (largest remainders)."""
    w = np.asarray(weights, np.float64)
    exact = n * w / w.sum()
    base = np.floor(exact).astype(np.int64)
    rest = n - base.sum()
    base[np.argsort(-(exact - base), kind="stable")[:rest]] += 1
    return base


def zipf_counts(count: int, s: float, n: int) -> np.ndarray:
    """How many of ``n`` requests go to each of ``count`` prefixes under
    Zipf popularity ``1 / rank**s`` (largest remainders)."""
    return shares(1.0 / np.arange(1, count + 1) ** s, n)


def class_sizes(classes: List[Dict[str, Any]], n: int, block: int,
                rng: np.random.Generator):
    """(prompt, output) lengths of ``n`` requests of several classes:
    each class's share of mid-quantile sizes, prompt and output paired
    at random within the class, then all in the block order of their
    prompt lengths."""
    counts = shares([c["weight"] for c in classes], n)
    plen, olen = [], []
    for c, k in zip(classes, counts):
        plen.append(quantile_sizes(c["prompt_tokens"], int(k)))
        olen.append(rng.permutation(quantile_sizes(c["output_tokens"],
                                                   int(k))))
    plen, olen = np.concatenate(plen), np.concatenate(olen)
    by_len = np.argsort(plen, kind="stable")
    rank = np.empty(n, np.int64)
    rank[by_len] = np.arange(n)
    pick = by_len[block_order(rank, block, rng)]
    return plen[pick], olen[pick]


def on_time(due: np.ndarray, bursts: Dict[str, Any]) -> np.ndarray:
    """Due times of a schedule that runs only in on-periods of
    ``on_s`` seconds, each followed by ``off_s`` seconds with none."""
    on, off = float(bursts["on_s"]), float(bursts["off_s"])
    k = np.floor(due / on)
    return k * (on + off) + (due - k * on)


@dataclasses.dataclass
class Req:
    index: int
    due_s: float            # open loop: due time from the window's start
    prompt: np.ndarray      # (plen,) int32
    max_new_tokens: int
    prefix: int             # shared prefix id, -1 for none


class ServeTraffic:
    """Requests of a serving mix, by index, from the seed."""

    def __init__(self, mix: Dict[str, Any], vocab: int, seed: int,
                 seconds: float):
        self.mix = mix
        self.vocab = int(vocab)
        self.seed = int(seed)
        self.loop = mix["loop"]
        self.preroll_s = float(mix.get("preroll_s", 0.0))
        if self.loop == "open":
            rate = float(mix["rate_per_s"])
            # the schedule covers pre-roll, window and a drain
            n = int(math.ceil(rate * (self.preroll_s + seconds) * 1.25)) + 16
        else:
            n = int(mix.get("pool", 256))
        self.n = n
        order = _rng(seed, _ORDER)
        blk = int(mix.get("order_block", 16))
        if mix.get("classes"):
            self._plen, self._olen = class_sizes(mix["classes"], n, blk,
                                                 order)
        else:
            self._plen = block_order(
                quantile_sizes(mix["prompt_tokens"], n), blk, order)
            self._olen = block_order(
                quantile_sizes(mix["output_tokens"], n), blk, order)
        self._due = None
        if self.loop == "open":
            gaps = block_order(exp_gaps(rate, n), blk, _rng(seed, _GAPS))
            due = np.cumsum(gaps) - gaps[0]
            if mix.get("bursts"):
                due = on_time(due, mix["bursts"])
            self._due = due - self.preroll_s
        sp = mix.get("shared_prefix")
        self._prefix = np.full(n, -1, np.int64)
        self._prefix_tokens: List[np.ndarray] = []
        if sp:
            counts = zipf_counts(sp["count"], sp["zipf_s"], n)
            ids = np.repeat(np.arange(sp["count"]), counts)
            self._prefix = _rng(seed, _ASSIGN).permutation(ids)
            for p in range(sp["count"]):
                self._prefix_tokens.append(_rng(seed, _PREFIX, p).integers(
                    0, self.vocab, sp["tokens"], dtype=np.int32))

    @property
    def prefix_tokens(self) -> int:
        sp = self.mix.get("shared_prefix")
        return int(sp["tokens"]) if sp else 0

    def _classes(self) -> List[Dict[str, Any]]:
        return self.mix.get("classes") or [self.mix]

    def max_prompt(self) -> int:
        return self.prefix_tokens + max(int(c["prompt_tokens"]["max"])
                                        for c in self._classes())

    def max_output(self) -> int:
        return max(int(c["output_tokens"]["max"]) for c in self._classes())

    def spread(self, k: int) -> List[int]:
        """Up to ``k`` requests (indices) of distinct prompt lengths,
        evenly spread over the request set sorted by length, the
        shortest and the longest among them."""
        by_len = np.argsort(self._plen, kind="stable")
        lens = self._plen[by_len]
        first = by_len[np.r_[True, lens[1:] != lens[:-1]]]
        pick = np.unique(np.rint(np.linspace(0, len(first) - 1,
                                             min(k, len(first)))))
        return [int(first[int(i)]) for i in pick]

    def __len__(self) -> int:
        return self.n if self.loop == "open" else 1 << 62

    def due_s(self, i: int) -> float:
        """When request ``i`` is due, from the window's start (open loop)."""
        return float(self._due[i % self.n])

    def request(self, i: int) -> Req:
        j = i % self.n
        user = _rng(self.seed, _PROMPT, i).integers(
            0, self.vocab, int(self._plen[j]), dtype=np.int32)
        p = int(self._prefix[j])
        prompt = user if p < 0 else np.concatenate(
            [self._prefix_tokens[p], user])
        due = float(self._due[j]) if self._due is not None else 0.0
        return Req(index=i, due_s=due, prompt=prompt,
                   max_new_tokens=int(self._olen[j]), prefix=p)


def train_batch(mix: Dict[str, Any], vocab: int, seed: int,
                step: int) -> np.ndarray:
    """Batch ``step`` of a training mix: ``(batch, seq)`` int32 ids."""
    return _rng(seed, _BATCH, step).integers(
        0, int(vocab), (int(mix["batch"]), int(mix["seq"])), dtype=np.int32)
