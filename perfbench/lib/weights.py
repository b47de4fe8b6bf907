"""Seeded random weights of a configuration, made on the device.

The weights are the benchmark's, not the program's: `make_weights` draws
them from the run's seed in a plain per-layer layout (every layer's leaf
stacked on a leading axis of `num_hidden_layers`), the reference reads
that layout, and `to_program_tree` hands the same model to the served
program in the layout and convention its loader
(`repro.models.model.Model`) expects.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .model_config import ModelConfig


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any whole number that fits 64 bits, as a traced
    value: one compiled program serves every seed."""
    return jnp.asarray([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                       jnp.uint32)


def make_weights(cfg: ModelConfig, key: jax.Array) -> dict:
    """float32 master weights drawn from `key` (a `seed_key`)."""
    d, f, n = cfg.hidden_size, cfg.intermediate_size, cfg.num_hidden_layers
    hq = cfg.num_attention_heads * cfg.head_dim
    hkv = cfg.num_key_value_heads * cfg.head_dim
    shapes = {
        "wq": (n, d, hq), "wk": (n, d, hkv), "wv": (n, d, hkv),
        "wo": (n, hq, d), "wg": (n, d, f), "wu": (n, d, f),
        "wd": (n, f, d),
    }
    names = sorted(shapes) + ["bq", "bk", "bv", "ln1", "ln2", "final_norm",
                              "embed", "lm_head"]
    keys = dict(zip(names, jax.random.split(key, len(names))))

    def normal(name, shape, std):
        return jax.random.normal(keys[name], shape, jnp.float32) * std

    w = {k: normal(k, s, s[1] ** -0.5) for k, s in shapes.items()}
    w["bq"] = normal("bq", (n, hq), 0.02)
    w["bk"] = normal("bk", (n, hkv), 0.02)
    w["bv"] = normal("bv", (n, hkv), 0.02)
    w["ln1"] = 1.0 + normal("ln1", (n, d), 0.05)
    w["ln2"] = 1.0 + normal("ln2", (n, d), 0.05)
    w["final_norm"] = 1.0 + normal("final_norm", (d,), 0.05)
    # the embedding at the head's scale: a tied head then gives logits of
    # unit scale, and no layer's input is dominated by its current token
    w["embed"] = normal("embed", (cfg.vocab_size, d), d ** -0.5)
    if not cfg.tie_word_embeddings:
        w["lm_head"] = normal("lm_head", (d, cfg.vocab_size), d ** -0.5)
    return w


def to_program_tree(cfg: ModelConfig, w: dict) -> dict:
    """The same model in the served program's parameter layout: one scan
    group per layer (`blocks/0/...` stacked on the layer axis), the
    vocabulary padded with zero rows to the program's padded width (the
    program masks those logits).

    The program multiplies the embedding rows it reads by
    sqrt(hidden_size), which Qwen2 does not, so its table holds the
    embedding divided by sqrt(hidden_size): the layers then see the
    published model's inputs. A tied head reads that same table, so its
    logits come out divided by sqrt(hidden_size), which leaves every
    greedy token as it is."""
    pad = cfg.padded_vocab - cfg.vocab_size
    table = jnp.pad(w["embed"], ((0, pad), (0, 0))) \
        * jnp.float32(cfg.hidden_size ** -0.5)
    if cfg.tie_word_embeddings:
        # the program reads the embedding table as its head when tied and
        # never this leaf, so it gets no memory
        head = jnp.zeros((1, 1), jnp.float32)
    else:
        head = jnp.pad(w["lm_head"], ((0, 0), (0, pad)))
    block = {
        "ln1": {"gamma_scale": w["ln1"]},
        "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo", "bq", "bk",
                                   "bv")},
        "ln2": {"gamma_scale": w["ln2"]},
        "mlp": {k: w[k] for k in ("wg", "wu", "wd")},
    }
    return {"embed": {"table": table},
            "final_norm": {"gamma_scale": w["final_norm"]},
            "lm_head": {"w_out": head},
            "blocks": {"0": block},
            "tail": []}
