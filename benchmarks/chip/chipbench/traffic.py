"""The one traffic generator: a mix file of parameters in, requests out.

A mix is a JSON file under ``traffic/``.  Its ``kind`` says how the load
is offered:

  ``closed``   ``clients`` callers, each sending its next request when the
               last one finished (batch generation);
  ``poisson``  an open loop of arrivals at ``rate_per_s``;
  ``train``    back-to-back training steps of ``batch`` x ``seq`` tokens.

Lengths come from ``{"dist": "lognormal", "median", "sigma", "min",
"max"}`` or ``{"dist": "uniform", "min", "max"}``.  Every seed draws the
same multiset of sizes and gaps, in another order: the sizes of each
block of ``stratum`` requests sit at the stratum midpoints of the
distribution, and the seed only shuffles them within the block and picks
the token ids.  So two seeds offer the same work, and the spread between
runs is the system's, not the sample's.
"""
from __future__ import annotations

import json
import math
import os
import statistics

import numpy as np

__all__ = ["load_mix", "lengths", "serve_requests", "arrival_offsets",
           "train_batches", "max_request_len", "page_counts", "mix_path"]

_NORMAL = statistics.NormalDist()


def load_mix(path: str) -> dict:
    with open(path) as f:
        mix = json.load(f)
    if mix.get("kind") not in ("closed", "poisson", "train"):
        raise ValueError(f"{path}: kind must be closed, poisson or train")
    return mix


def _quantile(dist: dict, q: float) -> int:
    if dist["dist"] == "lognormal":
        v = dist["median"] * math.exp(dist["sigma"] * _NORMAL.inv_cdf(q))
        return int(min(max(round(v), dist["min"]), dist["max"]))
    if dist["dist"] == "uniform":
        lo, hi = dist["min"], dist["max"]
        return int(lo + min(math.floor(q * (hi - lo + 1)), hi - lo))
    raise ValueError(f"unknown length distribution {dist['dist']!r}")


def _strata(stratum: int) -> np.ndarray:
    return (np.arange(stratum) + 0.5) / stratum


def lengths(dist: dict, n: int, stratum: int, rng: np.random.Generator
            ) -> np.ndarray:
    """``n`` lengths: blocks of ``stratum`` stratum midpoints, each block
    shuffled by ``rng``."""
    base = np.array([_quantile(dist, q) for q in _strata(stratum)], np.int64)
    blocks = [rng.permutation(base) for _ in range(-(-n // stratum))]
    return np.concatenate(blocks)[:n]


def page_counts(mix: dict, page: int) -> tuple[set, set]:
    """Page counts a serving engine meets under ``mix``: those of every
    prompt size (its prefill's pages), and those of every prompt and
    output size together (the pages a finished request releases: its
    prompt and every generated token but the last)."""
    st = mix.get("stratum", 64)
    ps = {_quantile(mix["prompt"], q) for q in _strata(st)}
    os_ = {_quantile(mix["output"], q) for q in _strata(st)}
    ceil = lambda n: -(-n // page)
    return ({ceil(p) for p in ps},
            {ceil(p + o - 1) for p in ps for o in os_})


def max_request_len(mix: dict) -> int:
    """Longest prompt plus output the mix can draw."""
    return int(mix["prompt"]["max"] + mix["output"]["max"])


def serve_requests(mix: dict, seed: int, n: int, vocab: int) -> list[dict]:
    """``n`` requests: ``{"idx", "prompt" (int32), "max_new_tokens"}``."""
    rng = np.random.default_rng([seed, 1])
    st = mix.get("stratum", 64)
    p = lengths(mix["prompt"], n, st, rng)
    o = lengths(mix["output"], n, st, rng)
    toks = np.random.default_rng([seed, 2])
    return [{"idx": i, "prompt": toks.integers(0, vocab, int(p[i]),
                                               dtype=np.int32),
             "max_new_tokens": int(o[i])} for i in range(n)]


def arrival_offsets(mix: dict, seed: int, n: int) -> np.ndarray:
    """Due times (s from the window's start) of ``n`` Poisson arrivals:
    exponential gaps at the stratum midpoints, shuffled per block."""
    rng = np.random.default_rng([seed, 3])
    st = mix.get("stratum", 64)
    gaps = -np.log1p(-_strata(st)) / float(mix["rate_per_s"])
    blocks = [rng.permutation(gaps) for _ in range(-(-n // st))]
    return np.cumsum(np.concatenate(blocks)[:n])


def train_batches(mix: dict, seed: int, vocab: int):
    """Endless seeded token batches ``{"tokens": (batch, seq) int32}``;
    step ``i``'s rows depend only on ``(seed, i)``."""
    i = 0
    while True:
        rng = np.random.default_rng([seed, 4, i])
        yield {"tokens": rng.integers(0, vocab, (mix["batch"], mix["seq"]),
                                      dtype=np.int32)}
        i += 1


def mix_path(root: str, name: str) -> str:
    return os.path.join(root, "traffic", f"{name}.json")
