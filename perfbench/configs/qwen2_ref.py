"""Plain reference of the Qwen2 architecture (Qwen1.5 and Qwen2 share it),
in float32 `jax.numpy` with no kernel, cache, batching or quantization.

It reads the benchmark's own weights (`perfbench/lib/weights.py`) and
imports nothing of the served program. Equations, per the published
Qwen2 description (arXiv:2407.10671):

    x_0 = E[tokens]
    h   = rms(x) * g1;  q, k, v = h Wq + bq, h Wk + bk, h Wv + bv
    q, k <- RoPE (rotate-half, theta = rope_theta)
    x  += softmax(q k^T / sqrt(head_dim) + causal mask) v  Wo   (GQA)
    h   = rms(x) * g2;  x += (silu(h Wg) * (h Wu)) Wd
    logits = rms(x_L) * g_f  @  (E^T if tied else W_head)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x: (T, H, D), positions 0..T-1."""
    t, _, d = x.shape
    half = d // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                    * (jnp.log(jnp.float32(theta)) / half))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def logits(cfg, w: dict, tokens: jax.Array, mm=None) -> jax.Array:
    """(T,) token ids -> (T, vocab_size) float32 next-token logits.

    `mm(a, b)` is the matrix product of every projection and of the head;
    by default float32 at full precision. The control passes a lower
    precision one."""
    if mm is None:
        def mm(a, b):
            return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    hd = cfg.head_dim
    nh, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
    eps = cfg.rms_norm_eps
    t = tokens.shape[0]
    x = w["embed"][tokens]
    causal = jnp.tril(jnp.ones((t, t), bool))

    def layer(x, p):
        h = _rms(x, p["ln1"], eps)
        q = (mm(h, p["wq"]) + p["bq"]).reshape(t, nh, hd)
        k = (mm(h, p["wk"]) + p["bk"]).reshape(t, nkv, hd)
        v = (mm(h, p["wv"]) + p["bv"]).reshape(t, nkv, hd)
        q, k = _rope(q, cfg.rope_theta), _rope(k, cfg.rope_theta)
        q = q.reshape(t, nkv, nh // nkv, hd)
        s = jnp.einsum("qhgd,khd->hgqk", q, k,
                       precision=jax.lax.Precision.HIGHEST) / jnp.sqrt(
                           jnp.float32(hd))
        s = jnp.where(causal, s, -jnp.inf)
        o = jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(s, axis=-1), v,
                       precision=jax.lax.Precision.HIGHEST)
        x = x + mm(o.reshape(t, nh * hd), p["wo"])
        h = _rms(x, p["ln2"], eps)
        x = x + mm(jax.nn.silu(mm(h, p["wg"])) * mm(h, p["wu"]), p["wd"])
        return x, None

    keys = ("ln1", "wq", "wk", "wv", "bq", "bk", "bv", "wo", "ln2", "wg",
            "wu", "wd")
    x, _ = jax.lax.scan(layer, x, {k: w[k] for k in keys})
    head = w["embed"].T if cfg.tie_word_embeddings else w["lm_head"]
    return mm(_rms(x, w["final_norm"], eps), head)
