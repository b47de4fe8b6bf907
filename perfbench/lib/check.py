"""The output check that decides `correct`.

After the window has closed and the program's state is freed, a sample
of the requests finished in the window, drawn from the seed and holding
the longest one, is run once through the configuration's plain float32
reference (`logits` of its architecture module) over prompt + served
tokens. Each served token is read at the position before it in two ways:
its gap, how far its reference logit lies below the reference's best
logit, and its rank, how many tokens the reference puts above it (both 0
where the program chose the reference's own greedy token).

The compared number is the median rank over the sample. On the random
weights of a cell W4A4 leaves few greedy tokens as the float32 reference
chooses them, so a gap sits near its ceiling for any precision: the mean
gap of a sound run and of a lower-precision control lie within 2x of
each other. At the top of a vocabulary-wide spread of logits the rank
grows much faster than the gap, so it separates them. PERF.md gives the
readings each limit was set from. The gaps are reported beside it.
"""
from __future__ import annotations

import numpy as np

from . import weights
from .model_config import architecture


def read_fn(cfg, mm=None):
    """jit(w, tokens (T,)) -> (gap (T,), rank (T,)) of tokens[t+1] at
    position t in the reference (the last entry is meaningless). With
    `mm`, the token judged at each position is the one a lower-precision
    reference puts first (the control)."""
    import jax
    import jax.numpy as jnp
    ref = architecture(cfg)

    def fn(w, toks):
        # a token outside the vocabulary reads as the reference's last
        # choice; the forward pass reads a valid id in its place
        valid = (toks >= 0) & (toks < cfg.vocab_size)
        toks = jnp.where(valid, toks, 0)
        full = ref.logits(cfg, w, toks)
        if mm is None:
            nxt = jnp.concatenate([toks[1:], toks[:1]])
            ok = jnp.concatenate([valid[1:], valid[:1]])
        else:
            nxt = jnp.argmax(ref.logits(cfg, w, toks, mm=mm), axis=-1)
            ok = True
        mine = jnp.where(ok, jnp.take_along_axis(full, nxt[:, None],
                                                 axis=-1)[:, 0],
                         jnp.min(full, axis=-1))
        return (jnp.max(full, axis=-1) - mine,
                jnp.sum(full > mine[:, None], axis=-1))
    return jax.jit(fn)


def sample(finished: list, k: int, seed: int) -> list:
    """k finished requests drawn from the seed, the longest among them."""
    if not finished:
        return []
    size = [len(f[1]) + len(f[2]) for f in finished]
    first = int(np.argmax(size))
    rest = [i for i in range(len(finished)) if i != first]
    rng = np.random.default_rng([seed, 1])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [finished[first]] + [finished[rest[i]] for i in sorted(pick)]


def served_readings(cfg, seed: int, finished: list, k: int, pad_to: int,
                    mm=None) -> dict:
    """{"gap": ..., "rank": ...} of every served token of the sampled
    requests (see module docstring); with `mm`, of the control's choice
    at the same positions."""
    import jax
    import jax.numpy as jnp
    w = jax.jit(lambda key: architecture(cfg).make_weights(cfg, key))(
        weights.seed_key(seed))
    fn = read_fn(cfg, mm)
    gaps, ranks = [], []
    for _, prompt, toks, _, _ in sample(finished, k, seed):
        seq = np.concatenate([prompt, np.asarray(toks, np.int32)])
        pad = np.zeros((pad_to,), np.int32)
        pad[:len(seq)] = seq
        g, r = (np.asarray(a) for a in fn(w, jnp.asarray(pad)))
        # served token j sits at len(prompt) + j, judged at the position
        # before it
        gaps.append(g[len(prompt) - 1:len(seq) - 1])
        ranks.append(r[len(prompt) - 1:len(seq) - 1])
    del w
    cat = (lambda a: np.concatenate(a) if a else np.zeros((0,)))
    return {"gap": cat(gaps), "rank": cat(ranks)}


def summarize(readings: dict, limits: dict) -> dict:
    """{check name: (value, limit)}; an empty sample reads None, which
    fails."""
    rank = readings["rank"]
    med = float(np.median(rank)) if rank.size else None
    return {"median_rank": (med, float(limits["median_rank"]))}


def gap_stats(readings: dict) -> dict:
    """The gaps, reported beside the compared number."""
    g = readings["gap"]
    return {"mean_gap": float(np.mean(g)) if g.size else None,
            "widest_gap": float(g.max()) if g.size else None}


def passes(checks: dict) -> bool:
    """Every (value, limit) of `checks` read, and within its limit."""
    return all(v is not None and v <= lim for v, lim in checks.values())
