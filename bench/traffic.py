"""The one traffic generator: a backlog of requests, read from a mix file.

A mix (bench/mixes/<name>.json) gives the number of requests, the token
lengths of each prompt and of its reply, and `stratify_block`.  Every
request is due at the start; prompts are unique random tokens, so no two
share a prefix.

Every size is drawn stratified: n values of a distribution are its
quantiles at (i + 0.5) / n, put in an order drawn from the seed.  So every
seed gives the same set of lengths in another order, and the work of a run
does not change with the seed.  The backlog is stratified in blocks
(`stratify_block`), so that every admission wave holds the same sizes.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

_NORMAL = NormalDist()


def stratified(rng: np.random.Generator, dist: dict, n: int,
               block: int = 0) -> np.ndarray:
    """n draws of `dist` ({"kind": ..., params}), quantiles in seeded order.
    With `block`, every run of `block` draws holds the distribution's
    `block` quantiles, so any prefix of whole blocks has the same sizes."""
    if block and n > block:
        parts = [stratified(rng, dist, min(block, n - i))
                 for i in range(0, n, block)]
        return np.concatenate(parts)
    u = (np.arange(n) + 0.5) / n
    kind = dist["kind"]
    if kind == "lognormal":
        z = np.array([_NORMAL.inv_cdf(x) for x in u])
        v = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
        v = np.clip(np.round(v), dist["min"], dist["max"])
    elif kind == "uniform":
        v = dist["min"] + np.floor(u * (dist["max"] - dist["min"] + 1))
    else:
        raise ValueError(f"unknown distribution kind {kind!r}")
    return v[rng.permutation(n)]


@dataclasses.dataclass
class Request:
    prompt: np.ndarray
    max_new: int


def backlog(mix: dict, seed: int, vocab: int, context: int) -> list[Request]:
    """The mix's requests, in the order they are submitted."""
    rng = np.random.default_rng([int(seed), 0x5E55])
    n = int(mix["requests"])
    blk = int(mix.get("stratify_block", 0))
    prompt = stratified(rng, mix["prompt_tokens"], n, blk).astype(int)
    reply = stratified(rng, mix["reply_tokens"], n, blk).astype(int)
    if int((prompt + reply).max()) > context:
        raise ValueError(f"a request of the mix passes the context "
                         f"({context} tokens)")
    return [Request(rng.integers(0, vocab, int(p)).astype(np.int32), int(r))
            for p, r in zip(prompt, reply)]
