"""Faults planted in the timed path, each of which the output check has
to catch: the toy-size test runs every one, and `perfbench/control.py
--faults` reads them at a cell's own size. A fault takes the engine after
it is built and wraps its jitted decode step (`engine._decode`, called as
`(params, caches, tokens, pos) -> (logits, caches)`)."""
from __future__ import annotations


def tokens_altered(eng) -> None:
    """Every sampled token is the one after the greedy choice."""
    import jax.numpy as jnp
    dec = eng._decode

    def step(params, caches, tokens, pos):
        logits, caches = dec(params, caches, tokens, pos)
        return jnp.roll(logits, 1, axis=-1), caches
    eng._decode = step


def state_unchanged(eng) -> None:
    """The decode step hands back the cache it was given: no decoded
    token's keys and values reach the paged cache."""
    dec = eng._decode

    def step(params, caches, tokens, pos):
        logits, _ = dec(params, caches, tokens, pos)
        return logits, caches
    eng._decode = step


def pages_swapped(eng) -> None:
    """Decode attention reads and writes the neighbouring slot's pages:
    the block table it is given is rolled by one row."""
    import jax.numpy as jnp
    from repro.serve.engine import ServingEngine
    dec = eng._decode

    def roll(site):
        return dict(site, block_table=jnp.roll(site["block_table"], 1,
                                               axis=-2))

    def keep_table(new, old):
        return dict(new, block_table=old["block_table"])

    def step(params, caches, tokens, pos):
        logits, new = dec(params, ServingEngine._map_sites(caches, roll),
                          tokens, pos)
        return logits, ServingEngine._pair_sites(new, caches, keep_table)
    eng._decode = step


FAULTS = {f.__name__: f for f in (tokens_altered, state_unchanged,
                                  pages_swapped)}
