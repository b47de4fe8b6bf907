"""The lower-precision references that a cell's output check has to fail
(PERF.md gives their readings).

The configuration states W4A4 with outlier-victim pairs (OVP): 4-bit
normal values, an outlier kept in its own slot and its neighbour pruned,
per-output-channel weight scales and one activation scale per tensor at
the 3-sigma rule. A control is the float32 reference with both operands
of every projection and of the LM head put through that same scheme at
fewer bits (`ovp_fake_quant`); the outlier is kept exactly, which favours
the control.
"""
from __future__ import annotations


def ovp_fake_quant(x, bits: int, pair_axis: int, scale):
    import jax.numpy as jnp
    n = 2 ** (bits - 1) - 1
    u = jnp.moveaxis(x / scale, pair_axis, -1)
    a, b = u[..., 0::2], u[..., 1::2]
    oa, ob = jnp.abs(a) > n, jnp.abs(b) > n
    fa = oa & (~ob | (jnp.abs(a) >= jnp.abs(b)))
    fb = ob & ~fa
    qa = jnp.where(fa, a, jnp.where(fb, 0.0, jnp.round(jnp.clip(a, -n, n))))
    qb = jnp.where(fb, b, jnp.where(fa, 0.0, jnp.round(jnp.clip(b, -n, n))))
    q = jnp.stack([qa, qb], -1).reshape(u.shape)
    return jnp.moveaxis(q, -1, pair_axis) * scale


def ovp_matmul(bits: int):
    """x (..., K) @ w (K, N) with both operands at `bits`-bit OVP."""
    import jax
    import jax.numpy as jnp
    n = 2 ** (bits - 1) - 1

    def mm(x, w):
        sx = jnp.maximum(3.0 * jnp.std(x) / n, 1e-8)
        sw = jnp.maximum(3.0 * jnp.std(w, axis=0, keepdims=True) / n, 1e-8)
        return jnp.matmul(ovp_fake_quant(x, bits, -1, sx),
                          ovp_fake_quant(w, bits, 0, sw),
                          precision=jax.lax.Precision.HIGHEST)
    return mm


CONTROLS = {"ovp3": lambda: ovp_matmul(3), "ovp2": lambda: ovp_matmul(2)}
