"""Pallas OVP encoder kernel (Algorithm 1 + Algorithm 2 in one pass).

Encodes scaled values u = x/scale into packed OVP bytes. Used on the serving
path to quantize activations online (the paper's quantization-unit-embedded
encoder, §3.1: "a thread handles two values simultaneously" — here one VPU
lane handles one byte = one pair).

Pairs run along the last axis: out byte (r, c) holds u[r, 2c] (high nibble)
and u[r, 2c+1] (low nibble). Codes are computed in int32 (Mosaic has no
shifts on uint8 vectors) and narrowed to bytes on the store.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.datatypes import ABFLOAT_FOR_NORMAL, AbfloatSpec, NORMAL_MAX


def _encode_normal_int4(u: jax.Array) -> jax.Array:
    q = jnp.clip(jnp.round(u), -7, 7).astype(jnp.int32)
    return q & 0xF


def _encode_abfloat4(u: jax.Array, spec: AbfloatSpec) -> jax.Array:
    sign = (u < 0).astype(jnp.int32)
    mag = jnp.clip(jnp.abs(u), spec.min_mag, spec.max_mag)
    exp = jnp.floor(jnp.log2(mag)).astype(jnp.int32) - spec.mb
    base = jnp.round(mag / jnp.exp2(exp.astype(jnp.float32))).astype(jnp.int32)
    ovf = base == (1 << (spec.mb + 1))
    exp = jnp.where(ovf, exp + 1, exp)
    base = jnp.where(ovf, 1 << spec.mb, base)
    efield = jnp.clip(exp - spec.bias, 0, (1 << spec.ebits) - 1)
    mfield = base & ((1 << spec.mb) - 1)
    code = (sign << 3) | (efield << spec.mb) | mfield
    zero_bits = (efield == 0) & (mfield == 0)
    return jnp.where(zero_bits, code | 1, code)


def encode_pair_planes(u0: jax.Array, u1: jax.Array,
                       spec: AbfloatSpec | None = None):
    """Scaled even/odd value planes -> int32 int4 OVP code planes
    (Algorithm 1: per pair at most one outlier survives as abfloat, its
    neighbour becomes the identifier). Shared by the encoder kernel and
    the fused prefill's cache write; codes match `core.ovp` bit for bit."""
    spec = ABFLOAT_FOR_NORMAL["int4"] if spec is None else spec
    nmax = float(NORMAL_MAX["int4"])
    a0, a1 = jnp.abs(u0), jnp.abs(u1)
    o0, o1 = a0 > nmax, a1 > nmax
    first_out = o0 & (~o1 | (a0 >= a1))
    second_out = o1 & ~first_out

    n0, n1 = _encode_normal_int4(u0), _encode_normal_int4(u1)
    f0, f1 = _encode_abfloat4(u0, spec), _encode_abfloat4(u1, spec)
    ident = 0x8
    c0 = jnp.where(first_out, f0, jnp.where(second_out, ident, n0))
    c1 = jnp.where(second_out, f1, jnp.where(first_out, ident, n1))
    return c0, c1


def pack_pair_planes(c0: jax.Array, c1: jax.Array) -> jax.Array:
    """int32 code planes -> packed bytes (even code in the high nibble)."""
    return ((c0 << 4) | (c1 & 0xF)).astype(jnp.uint8)


def _encode_kernel(u_ref, o_ref, *, spec):
    o_ref[...] = pack_pair_planes(*encode_pair_planes(
        u_ref[0].astype(jnp.float32), u_ref[1].astype(jnp.float32), spec))


def ovp_encode_pallas(u: jax.Array, normal_dtype: str = "int4",
                      spec: AbfloatSpec | None = None,
                      bm: int = 256, bk: int = 512,
                      interpret: bool = False) -> jax.Array:
    """u: (M, K) scaled values -> (M, K/2) packed uint8. int4 normals only
    (the serving activation path; flint4 activations are not used by the
    paper either). The even/odd split runs in XLA before the kernel: an
    in-kernel lane-strided slice does not lower on the TPU."""
    assert normal_dtype == "int4", "encoder kernel targets int4 activations"
    spec = ABFLOAT_FOR_NORMAL[normal_dtype] if spec is None else spec
    m, k = u.shape
    bm, bk = min(bm, m), min(bk, k)
    bk2 = bk // 2
    grid = (m // bm, (k // 2) // bk2)
    planes = jnp.stack([u[:, 0::2], u[:, 1::2]])
    kernel = functools.partial(_encode_kernel, spec=spec)
    # `name` is the op's name in the device trace (`ovp_encode.<n>`)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((2, bm, bk2), lambda i, j: (0, i, j))],
        out_specs=pl.BlockSpec((bm, bk2), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, k // 2), jnp.uint8),
        interpret=interpret,
        name="ovp_encode",
    )(planes)
