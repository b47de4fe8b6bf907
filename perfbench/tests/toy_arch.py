"""A second architecture, for tests only: a Llama-style block (no QKV
bias) whose heads are wider than hidden_size / num_attention_heads, with
its own leaf names. It gives the six functions a configuration's
architecture module gives, so that the harness in `perfbench/lib` serves
and checks it as it does Qwen2, knowing no leaf or projection by name.

    h = rms(x) * g_in;  q, k, v = h Wq, h Wk, h Wv;  q, k <- RoPE
    x += softmax(q k^T / sqrt(head_dim) + causal mask) v  Wo   (GQA)
    h = rms(x) * g_post;  x += (silu(h Wgate) * (h Wup)) Wdown
    logits = rms(x_L) * g_norm  @  W_head
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

PROJECTIONS = ("q_proj", "k_proj", "v_proj", "o_proj", "gate_proj",
               "up_proj", "down_proj")


def _shapes(cfg) -> dict:
    d, f = cfg.hidden_size, cfg.intermediate_size
    hq = cfg.num_attention_heads * cfg.head_dim
    hkv = cfg.num_key_value_heads * cfg.head_dim
    return {"q_proj": (d, hq), "k_proj": (d, hkv), "v_proj": (d, hkv),
            "o_proj": (hq, d), "gate_proj": (d, f), "up_proj": (d, f),
            "down_proj": (f, d)}


def make_weights(cfg, key):
    n, d = cfg.num_hidden_layers, cfg.hidden_size
    names = PROJECTIONS + ("input_norm", "post_norm", "norm", "embed",
                           "head")
    keys = dict(zip(names, jax.random.split(key, len(names))))

    def normal(name, shape, std):
        return jax.random.normal(keys[name], shape, jnp.float32) * std

    w = {k: normal(k, (n, *s), s[0] ** -0.5)
         for k, s in _shapes(cfg).items()}
    w["input_norm"] = 1.0 + normal("input_norm", (n, d), 0.05)
    w["post_norm"] = 1.0 + normal("post_norm", (n, d), 0.05)
    w["norm"] = 1.0 + normal("norm", (d,), 0.05)
    w["embed"] = normal("embed", (cfg.vocab_size, d), d ** -0.5)
    w["head"] = normal("head", (d, cfg.vocab_size), d ** -0.5)
    return w


def to_program_tree(cfg, w):
    """The program's layout; its table holds the embedding divided by
    sqrt(hidden_size), which the program multiplies back."""
    pad = cfg.padded_vocab - cfg.vocab_size
    names = dict(zip(("wq", "wk", "wv", "wo", "wg", "wu", "wd"),
                     PROJECTIONS))
    block = {
        "ln1": {"gamma_scale": w["input_norm"]},
        "attn": {p: w[names[p]] for p in ("wq", "wk", "wv", "wo")},
        "ln2": {"gamma_scale": w["post_norm"]},
        "mlp": {p: w[names[p]] for p in ("wg", "wu", "wd")},
    }
    return {"embed": {"table": jnp.pad(w["embed"], ((0, pad), (0, 0)))
                      * jnp.float32(cfg.hidden_size ** -0.5)},
            "final_norm": {"gamma_scale": w["norm"]},
            "lm_head": {"w_out": jnp.pad(w["head"], ((0, 0), (0, pad)))},
            "blocks": {"0": block},
            "tail": []}


def program_sizes(cfg) -> dict:
    return dict(n_layers=cfg.num_hidden_layers, d_model=cfg.hidden_size,
                n_heads=cfg.num_attention_heads,
                n_kv_heads=cfg.num_key_value_heads,
                d_ff=cfg.intermediate_size, vocab=cfg.vocab_size,
                head_dim=cfg.head_dim, qkv_bias=False,
                rope_theta=cfg.rope_theta, norm_eps=cfg.rms_norm_eps,
                tie_embeddings=False)


def layer_matmuls(cfg) -> list:
    return [(name, *_shapes(cfg)[name]) for name in PROJECTIONS]


def flops_per_token(cfg, context: float) -> float:
    weights = sum(k * n for _, k, n in layer_matmuls(cfg)) \
        * cfg.num_hidden_layers + cfg.hidden_size * cfg.vocab_size
    return 2.0 * weights + 4.0 * context * cfg.num_attention_heads \
        * cfg.head_dim * cfg.num_hidden_layers


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, theta):
    t, _, d = x.shape
    half = d // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def logits(cfg, w, tokens, mm=None):
    if mm is None:
        def mm(a, b):
            return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    hd, eps = cfg.head_dim, cfg.rms_norm_eps
    nh, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
    t = tokens.shape[0]
    causal = jnp.tril(jnp.ones((t, t), bool))

    def layer(x, p):
        h = _rms(x, p["input_norm"], eps)
        q = _rope(mm(h, p["q_proj"]).reshape(t, nh, hd), cfg.rope_theta)
        k = _rope(mm(h, p["k_proj"]).reshape(t, nkv, hd), cfg.rope_theta)
        v = mm(h, p["v_proj"]).reshape(t, nkv, hd)
        k, v = (jnp.repeat(a, nh // nkv, axis=1) for a in (k, v))
        s = jnp.einsum("qhd,khd->hqk", q, k,
                       precision=jax.lax.Precision.HIGHEST) / jnp.sqrt(
                           jnp.float32(hd))
        a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", a, v,
                       precision=jax.lax.Precision.HIGHEST)
        x = x + mm(o.reshape(t, nh * hd), p["o_proj"])
        h = _rms(x, p["post_norm"], eps)
        return x + mm(jax.nn.silu(mm(h, p["gate_proj"]))
                      * mm(h, p["up_proj"]), p["down_proj"]), None

    stacked = {k: w[k] for k in PROJECTIONS + ("input_norm", "post_norm")}
    x, _ = jax.lax.scan(layer, w["embed"][tokens], stacked)
    return mm(_rms(x, w["norm"], eps), w["head"])
