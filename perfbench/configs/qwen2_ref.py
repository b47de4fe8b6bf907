"""The Qwen2 architecture as the benchmark knows it (Qwen1.5 and Qwen2
share it): its seeded weights, the served program's layout and sizes of
them, the fused-matmul calls and operations of one forward, and the plain
float32 reference in `jax.numpy` with no kernel, cache, batching or
quantization.

A configuration file names this module under `reference`; the harness in
`perfbench/lib` calls these six functions and knows no leaf or
projection by name. Nothing here imports the served program. Equations of
the reference, per the published Qwen2 description (arXiv:2407.10671):

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


def make_weights(cfg, key: jax.Array) -> dict:
    """float32 master weights drawn from `key` (`perfbench.lib.weights.
    seed_key`), in a plain per-layer layout: every layer's leaf stacked
    on a leading axis of `num_hidden_layers`."""
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


def to_program_tree(cfg, w: dict) -> dict:
    """The same model in the served program's parameter layout
    (`repro.models.model.Model`): one scan group per layer (`blocks/0/...`
    stacked on the layer axis), the vocabulary padded with zero rows to
    the program's padded width (the program masks those logits).

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


def program_sizes(cfg) -> dict:
    """The fields of the program's `ArchConfig` (named by the file's
    `program.arch`) that this configuration sets."""
    return dict(n_layers=cfg.num_hidden_layers, d_model=cfg.hidden_size,
                n_heads=cfg.num_attention_heads,
                n_kv_heads=cfg.num_key_value_heads,
                d_ff=cfg.intermediate_size, vocab=cfg.vocab_size,
                head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
                norm_eps=cfg.rms_norm_eps,
                tie_embeddings=cfg.tie_word_embeddings)


def layer_matmuls(cfg) -> list[tuple[str, int, int]]:
    """(site, K, N) of the fused OVP matmul calls of one layer: the seven
    quantized projections."""
    d, f = cfg.hidden_size, cfg.intermediate_size
    hq = cfg.num_attention_heads * cfg.head_dim
    hkv = cfg.num_key_value_heads * cfg.head_dim
    return [("wq", d, hq), ("wk", d, hkv), ("wv", d, hkv), ("wo", hq, d),
            ("wg", d, f), ("wu", d, f), ("wd", f, d)]


def flops_per_token(cfg, context: float) -> float:
    """Model operations to generate one token at `context` positions of
    history: 2 per weight of every projection and of the LM head, plus
    the attention scores and the weighted sum over the context."""
    weights = sum(k * n for _, k, n in layer_matmuls(cfg)) \
        * cfg.num_hidden_layers + cfg.hidden_size * cfg.vocab_size
    attn = 4.0 * context * cfg.num_attention_heads * cfg.head_dim \
        * cfg.num_hidden_layers
    return 2.0 * weights + attn


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
