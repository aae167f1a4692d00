"""The one traffic generator: a closed-loop backlog built from a mix file.

A mix file (``traffic/<name>.json``) holds only parameters:

    {"clients": 8,
     "prompt": {"source": "passage" | "stream", "lengths": [16]},
     "output": {"median": 96, "sigma": 0.6, "min": 16, "max": 256,
                "pool": 64}}

Output lengths come from a fixed pool: the lognormal's quantiles at
``(i + 0.5) / pool``, clipped to [min, max]. A backlog of whole cycles
through the pool holds the same sizes for every seed, and the same prompt
lengths, in the seed's own order: a seed changes which requests come when
and what they say, never how much work they are.

Prompt sources (the corpus the stack built from the seed):
  * ``passage``: question-like prompts, tokens drawn from one target passage
    (the topic model's own words), as ``training.data.make_queries`` does;
  * ``stream``: a contiguous slice of a held-out token stream of the same
    topic model (not rows of any datastore)."""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import List, Tuple

import numpy as np


def output_pool(mix: dict) -> List[int]:
    o = mix["output"]
    nd = NormalDist(math.log(o["median"]), o["sigma"])
    n = int(o.get("pool", 64))
    return [int(min(o["max"], max(o["min"], round(math.exp(nd.inv_cdf((i + .5) / n))))))
            for i in range(n)]


def max_output(mix: dict) -> int:
    return max(output_pool(mix))


def rng(seed: int, stream: str) -> np.random.Generator:
    """Independent generator per (seed, purpose); any non-negative seed."""
    return np.random.default_rng([int(seed), *stream.encode()])


def make_requests(mix: dict, corpus, seed: int, n: int,
                  stream: str = "window") -> List[Tuple[list, int]]:
    """``n`` requests (prompt tokens, max new tokens) for this seed."""
    g = rng(seed, stream)
    pool = output_pool(mix)
    lens = list(mix["prompt"]["lengths"])
    outs = [pool[i] for i in np.concatenate(
        [g.permutation(len(pool)) for _ in range(-(-n // len(pool)))])[:n]]
    plens = [lens[i % len(lens)] for i in g.permutation(max(n, len(lens)))][:n]
    src = mix["prompt"]["source"]
    reqs = []
    for p_len, out in zip(plens, outs):
        if src == "passage":
            doc = corpus.passages[g.integers(0, corpus.passages.shape[0])]
            prompt = doc[g.integers(0, doc.shape[0], size=p_len)]
        elif src == "stream":
            start = g.integers(0, corpus.heldout.shape[0] - p_len)
            prompt = corpus.heldout[start:start + p_len]
        else:
            raise ValueError(f"unknown prompt source {src!r}")
        reqs.append(([int(t) for t in prompt], int(out)))
    return reqs
