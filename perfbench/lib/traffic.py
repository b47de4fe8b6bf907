"""The one traffic generator: reads a mix's data file and makes its
requests from the seed.

Every seed gets the same prompt and output lengths in the same order
(fixed quantiles of the mix's distributions, paired and ordered by a fixed
permutation); the seed draws the token ids. A window holds only the first
few hundred steps of a closed loop, so which requests come first sets
its work: with the order drawn from the seed, runs on different seeds
spread by several percent where runs on one seed agree to a fraction of
one.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parents[1] / "traffic"


def load(name: str) -> dict:
    spec = json.loads((TRAFFIC_DIR / f"{name}.json").read_text())
    spec["name"] = name
    return spec


def _quantiles(dist: dict, n: int) -> np.ndarray:
    """n lengths at the midpoints of n equal-probability strata."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = float(dist["min"]), float(dist["max"])
    if dist["dist"] == "uniform":
        x = lo + u * (hi - lo)
    elif dist["dist"] == "log_uniform":
        x = np.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def lengths(spec: dict) -> tuple[np.ndarray, np.ndarray]:
    """(prompt lengths, output lengths) of the mix, in the order clients
    send them."""
    n = int(spec["requests"])
    rng = np.random.default_rng(0)
    prompt = _quantiles(spec["prompt_len"], n)[rng.permutation(n)]
    output = _quantiles(spec["output_len"], n)[rng.permutation(n)]
    return prompt, output


def requests(spec: dict, vocab: int, seed: int) -> list[tuple[np.ndarray, int]]:
    """[(prompt token ids, new tokens to generate)] in the order clients
    send them."""
    prompt, output = lengths(spec)
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, vocab, int(p)).astype(np.int32), int(o))
            for p, o in zip(prompt, output)]


def warmup_prompt_lengths(spec: dict) -> list[int]:
    """One prompt length in each power-of-two band the mix's prompts span:
    the shortest, then every power of two above it up to the longest."""
    lo, hi = int(spec["prompt_len"]["min"]), int(spec["prompt_len"]["max"])
    out, p = [lo], 1
    while p <= hi:
        if p > lo:
            out.append(p)
        p *= 2
    if out[-1] < hi and (hi & (hi - 1)):
        out.append(hi)
    return out
