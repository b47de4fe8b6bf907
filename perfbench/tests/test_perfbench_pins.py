"""What the harness gives for the Qwen2 configurations, pinned: the tiny
configuration's weights and program tree leaf by leaf at two seeds, and
for both real configurations the program's sizes, the shapes of both
trees (from `jax.eval_shape`, so no real-width weight is drawn here), the
fused-matmul calls of a step and the operations per token. The numbers
were taken before the architecture code moved out of `perfbench/lib`
into the configuration's own module; every reading of both cells rests
on them staying as they are."""
import hashlib

import jax
import numpy as np
import pytest

from perfbench.lib import bench, cell, cost, weights
from perfbench.tests import tiny


def _leaves(tree):
    return [(jax.tree_util.keystr(p), v)
            for p, v in jax.tree_util.tree_flatten_with_path(tree)[0]]


def leaf_digests(tree) -> dict:
    """{leaf path: sha256 of its shape, dtype and bytes (16 hex digits)}."""
    return {p: hashlib.sha256(f"{v.shape}{v.dtype}".encode()
                              + np.asarray(v).tobytes()).hexdigest()[:16]
            for p, v in _leaves(tree)}


def shape_digest(tree) -> str:
    """sha256 of every leaf's path, shape and dtype (16 hex digits)."""
    text = "".join(f"{p} {tuple(v.shape)} {v.dtype}\n"
                   for p, v in _leaves(tree))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def tiny_trees(seed: int):
    cfg, key = tiny.CONFIG, weights.seed_key(seed)
    w = jax.jit(lambda k: weights.make_weights(cfg, k))(key)
    t = jax.jit(lambda k: weights.to_program_tree(
        cfg, weights.make_weights(cfg, k)))(key)
    return w, t


def real_shapes(cfg):
    key = weights.seed_key(1)
    w = jax.eval_shape(lambda k: weights.make_weights(cfg, k), key)
    t = jax.eval_shape(lambda k: weights.to_program_tree(
        cfg, weights.make_weights(cfg, k)), key)
    return shape_digest(w), shape_digest(t)


TINY_WEIGHTS = {
    7: {
        "['bk']": "f5637e0428d08aa5",
        "['bq']": "799f6671c07ed2f2",
        "['bv']": "31096255ebde3054",
        "['embed']": "ddd8942008a1fc15",
        "['final_norm']": "0bb6b800e2c37e2a",
        "['lm_head']": "bc85e8b8829d0d0b",
        "['ln1']": "90ebbc4e0cd3089b",
        "['ln2']": "4ee5224027b7503c",
        "['wd']": "dfa649d7b2a16eaa",
        "['wg']": "ccc4138691a307e6",
        "['wk']": "a7ebdbe579071007",
        "['wo']": "434fe6ccb1b4b281",
        "['wq']": "817b4bc0c2fcef95",
        "['wu']": "df07b7fec0f70383",
        "['wv']": "f64c00b4105d3a87",
    },
    8589934597: {
        "['bk']": "7fcd698e5e9baa51",
        "['bq']": "86d2e3e4308609fc",
        "['bv']": "85a018fd9baeb145",
        "['embed']": "6ad89171bcb867c4",
        "['final_norm']": "0ca24449ab0b8066",
        "['lm_head']": "34c313025f1bacf2",
        "['ln1']": "b96da0b7dab837f4",
        "['ln2']": "62c55f55dc72b807",
        "['wd']": "c59f64671eac83db",
        "['wg']": "ec503a2f71ee0db0",
        "['wk']": "ed814003961ad4b6",
        "['wo']": "bb068d3b2abdc6c7",
        "['wq']": "909d48ce8dffbe84",
        "['wu']": "34ba9051616d7a4b",
        "['wv']": "c7389d99f2698e4c",
    },
}
TINY_TREE = {
    7: {
        "['blocks']['0']['attn']['bk']": "f5637e0428d08aa5",
        "['blocks']['0']['attn']['bq']": "799f6671c07ed2f2",
        "['blocks']['0']['attn']['bv']": "31096255ebde3054",
        "['blocks']['0']['attn']['wk']": "a7ebdbe579071007",
        "['blocks']['0']['attn']['wo']": "434fe6ccb1b4b281",
        "['blocks']['0']['attn']['wq']": "817b4bc0c2fcef95",
        "['blocks']['0']['attn']['wv']": "f64c00b4105d3a87",
        "['blocks']['0']['ln1']['gamma_scale']": "90ebbc4e0cd3089b",
        "['blocks']['0']['ln2']['gamma_scale']": "4ee5224027b7503c",
        "['blocks']['0']['mlp']['wd']": "dfa649d7b2a16eaa",
        "['blocks']['0']['mlp']['wg']": "ccc4138691a307e6",
        "['blocks']['0']['mlp']['wu']": "df07b7fec0f70383",
        "['embed']['table']": "b0d7111fe2ebc491",
        "['final_norm']['gamma_scale']": "0bb6b800e2c37e2a",
        "['lm_head']['w_out']": "53a54e8eee28ba67",
    },
    8589934597: {
        "['blocks']['0']['attn']['bk']": "7fcd698e5e9baa51",
        "['blocks']['0']['attn']['bq']": "86d2e3e4308609fc",
        "['blocks']['0']['attn']['bv']": "85a018fd9baeb145",
        "['blocks']['0']['attn']['wk']": "ed814003961ad4b6",
        "['blocks']['0']['attn']['wo']": "bb068d3b2abdc6c7",
        "['blocks']['0']['attn']['wq']": "909d48ce8dffbe84",
        "['blocks']['0']['attn']['wv']": "c7389d99f2698e4c",
        "['blocks']['0']['ln1']['gamma_scale']": "b96da0b7dab837f4",
        "['blocks']['0']['ln2']['gamma_scale']": "62c55f55dc72b807",
        "['blocks']['0']['mlp']['wd']": "c59f64671eac83db",
        "['blocks']['0']['mlp']['wg']": "ec503a2f71ee0db0",
        "['blocks']['0']['mlp']['wu']": "34ba9051616d7a4b",
        "['embed']['table']": "dd834389b05fb821",
        "['final_norm']['gamma_scale']": "0ca24449ab0b8066",
        "['lm_head']['w_out']": "9df45d9d942f29df",
    },
}
REAL = {
    "qwen1.5-0.5b.decode_closed": {
        "arch": {"n_layers": 24, "d_model": 1024, "n_heads": 16,
                 "n_kv_heads": 16, "d_ff": 2816, "vocab": 151936,
                 "head_dim": 64, "rope_theta": 1000000.0, "norm_eps": 1e-06,
                 "tie_embeddings": True},
        "shapes": ("d00b3b6dbb0ea571", "09c5f2b4ed28cb6b"),
        "step_matmuls": [(32, 1024, 1024), (32, 1024, 1024), (32, 1024, 1024),
                         (32, 1024, 1024), (32, 1024, 2816), (32, 1024, 2816),
                         (32, 2816, 1024)] * 24,
        "flops_per_token_500": 976879616.0,
    },
    "qwen2-7b.decode_closed": {
        "arch": {"n_layers": 7, "d_model": 3584, "n_heads": 28,
                 "n_kv_heads": 4, "d_ff": 18944, "vocab": 152064,
                 "head_dim": 128, "rope_theta": 1000000.0, "norm_eps": 1e-06,
                 "tie_embeddings": False},
        "shapes": ("7748fb475d9b5ad9", "321ea7a95ed8cf77"),
        "step_matmuls": [(32, 3584, 3584), (32, 3584, 512), (32, 3584, 512),
                         (32, 3584, 3584), (32, 3584, 18944),
                         (32, 3584, 18944), (32, 18944, 3584)] * 7,
        "flops_per_token_500": 4402814976.0,
    },
}


@pytest.mark.parametrize("seed", sorted(TINY_WEIGHTS))
def test_tiny_weights_are_drawn_as_pinned(seed):
    w, _ = tiny_trees(seed)
    assert leaf_digests(w) == TINY_WEIGHTS[seed]


@pytest.mark.parametrize("seed", sorted(TINY_TREE))
def test_tiny_program_tree_is_laid_out_as_pinned(seed):
    _, t = tiny_trees(seed)
    assert leaf_digests(t) == TINY_TREE[seed]


@pytest.mark.parametrize("name", sorted(REAL))
def test_program_sizes_are_as_pinned(name):
    bench.import_program()
    from repro.configs import get_config
    cfg = cell.load(name).config
    arch = bench.arch_config(cfg)
    pinned = REAL[name]["arch"]
    assert {k: getattr(arch, k) for k in pinned} == pinned
    # every other field is the program's own
    base = get_config(cfg.arch)
    for f in arch.__dataclass_fields__:
        if f not in pinned:
            assert getattr(arch, f) == getattr(base, f), f


@pytest.mark.parametrize("name", sorted(REAL))
def test_tree_shapes_are_as_pinned(name):
    assert real_shapes(cell.load(name).config) == REAL[name]["shapes"]


@pytest.mark.parametrize("name", sorted(REAL))
def test_step_matmuls_are_as_pinned(name):
    assert cost.step_ovp_matmuls(cell.load(name).config, 32) \
        == REAL[name]["step_matmuls"]


@pytest.mark.parametrize("name", sorted(REAL))
def test_flops_per_token_is_as_pinned(name):
    assert cost.flops_per_token(cell.load(name).config, 500) \
        == REAL[name]["flops_per_token_500"]
