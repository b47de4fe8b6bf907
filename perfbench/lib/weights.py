"""Seeded random weights of a configuration, made on the device.

The weights are the benchmark's, not the program's: the configuration's
architecture module (`model_config.architecture`) draws them from the
run's seed in a plain layout, the reference reads that layout, and its
`to_program_tree` hands the same model to the served program in the
layout and convention the program's loader expects.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .model_config import ModelConfig, architecture


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any whole number that fits 64 bits, as a traced
    value: one compiled program serves every seed."""
    return jnp.asarray([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                       jnp.uint32)


def make_weights(cfg: ModelConfig, key: jax.Array) -> dict:
    """float32 master weights drawn from `key` (a `seed_key`)."""
    return architecture(cfg).make_weights(cfg, key)


def to_program_tree(cfg: ModelConfig, w: dict) -> dict:
    """`make_weights`' model in the served program's parameter layout."""
    return architecture(cfg).to_program_tree(cfg, w)
