"""Fused OVP matmul Pallas kernel (the paper's decoder + encoder, §3–4,
re-sited for TPU).

TPU adaptation of the OliVe datapath: on the GPU/systolic designs the OVP
decoder sits per dot-product lane and the encoder inside the quantization
unit. The MXU is fixed function, so both become *phases of one matmul
kernel*:

  prologue  — activations are either decoded from packed OVP bytes
              (pre-quantized operands) or OVP-quantized in value domain
              straight from the fp tile (online serving: no packed
              activation tensor ever touches HBM),
  body      — packed uint8 weight tiles stream HBM->VMEM (4x less traffic
              than bf16), nibbles/bytes are decoded branch-free on the VPU,
              and the MXU consumes the decoded tiles,
  epilogue  — per-row activation scales and per-output-channel weight
              scales are applied to the fp32 accumulator on the last
              K step (no separate XLA multiply dispatch).

Key structural trick: pairs are packed along K, so a packed tile holds the
even-K values in the high nibbles and odd-K values in the low nibbles.
Instead of interleaving (a relayout), we split the reduction:

    out = a_even @ w_even + a_odd @ w_odd

two half-K MXU matmuls per tile, no transposes, no gathers — this is the
memory-alignment claim of the paper realised on TPU. Operands that are not
nibble-packed (fp/quantize activations, int8 codes) reach the kernel as a
stacked (2, …, K/2) even/odd plane pair: the wrapper deinterleaves them
with one XLA slice, because Mosaic lowers an in-kernel lane- or
sublane-strided slice as a gather and refuses it. Packed operands ride
the same layout with a plane axis of 1.

The grid is (batch, M/bm, N/bn, K2/bk2) with K innermost, so a 3-D lhs
(decode-step GEMMs from the serving engine) hits the kernel without any
reshape glue; 2-D callers pass batch=1.

Blocks default to (bm, bk, bn) = (128, 256, 128): MXU-aligned, and the
working set (a: 128x256 f32 + w packed: 128x128 u8 + out: 128x128 f32)
is ~200 KiB, far inside VMEM; bk can grow to 2048 before VMEM pressure.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.datatypes import (ABFLOAT_FOR_NORMAL, ID4, ID8, NORMAL_MAX,
                                  AbfloatSpec, abfloat_decode, abfloat_encode,
                                  int_normal_decode)

# Activation operand modes of the fused kernel (static):
#   fp        — fp tile used as-is (W4A16 / W8A16)
#   quantize  — fp tile OVP fake-quantized in the prologue at the per-row
#               scale (online W4A4 / W8A8 serving: no packed tensor in HBM)
#   codes4    — packed nibble codes, decoded in the prologue
#   codes8    — int8 OVP codes (one per byte), decoded in the prologue
# "quantize" has a *static-scale* twin (`a_static=True`, the
# `_*_kernel_static` bodies): the calibrated per-site scale arrives as a
# single (1, 1) scalar operand instead of the (B, M, 1) per-row stream,
# so one compiled kernel serves every calibrated site and no per-step 3σ
# std runs upstream.
ACT_MODES = ("fp", "quantize", "codes4", "codes8")


# --------------------------------------------------------------------------
# Branch-free decode (VPU-friendly: selects + integer shifts only)
# --------------------------------------------------------------------------
def _decode_normal_int4(c: jax.Array) -> jax.Array:
    ci = c.astype(jnp.int32)
    return jnp.where(ci >= 8, ci - 16, ci).astype(jnp.float32)


def _decode_normal_flint4(c: jax.Array) -> jax.Array:
    ci = c.astype(jnp.int32)
    idx = ci & 0x7
    mag = jnp.where(idx <= 4, idx,
                    jnp.where(idx == 5, 6, jnp.where(idx == 6, 8, 16)))
    sign = jnp.where((ci >> 3) == 1, -1, 1)
    return (sign * mag).astype(jnp.float32)


def _decode_normal_int8(c: jax.Array) -> jax.Array:
    # the datatypes decoder is already a branch-free where-chain, safe
    # inside the kernel body (unlike LUT gathers)
    return int_normal_decode(c, 8)


_NORMAL_DECODERS = {"int4": _decode_normal_int4,
                    "flint4": _decode_normal_flint4,
                    "int8": _decode_normal_int8}


def _decode_abfloat(c: jax.Array, spec: AbfloatSpec) -> jax.Array:
    """Fig. 7 decoder: exponent = bias + e-bits; integer = (1 m)b.

    Pure shifts + selects (§3.3); magnitudes clamp at 2^15 (§4.5) to match
    `datatypes.abfloat_decode` for the wide int8/E4M3 spec.
    """
    ci = c.astype(jnp.int32)
    nbits = spec.ebits + spec.mb
    bits = ci & ((1 << nbits) - 1)
    e = bits >> spec.mb
    m = bits & ((1 << spec.mb) - 1)
    mag = ((1 << spec.mb) + m) << (e + spec.bias)    # pure shifts, §3.3
    mag = jnp.minimum(mag, 1 << 15)
    v = jnp.where((ci >> nbits) & 1 == 1, -mag, mag)
    return jnp.where(bits == 0, 0, v).astype(jnp.float32)


def decode_pair_planes(c0: jax.Array, c1: jax.Array, normal_dtype: str,
                       spec: AbfloatSpec):
    """Two code planes (pair-mates) -> decoded fp32 planes.

    If my neighbour holds the identifier, I am the outlier (abfloat); if I
    hold it, I am the victim (0); otherwise I am a normal value.
    """
    c0, c1 = c0.astype(jnp.int32), c1.astype(jnp.int32)
    ident = ID8 if normal_dtype == "int8" else ID4
    dn = _NORMAL_DECODERS[normal_dtype]

    def slot(c, neighbour):
        return jnp.where(neighbour == ident, _decode_abfloat(c, spec),
                         jnp.where(c == ident, 0.0, dn(c)))

    return slot(c0, c1), slot(c1, c0)


def decode_nibble_planes(packed: jax.Array, normal_dtype: str,
                         spec: AbfloatSpec):
    """packed (R, C) uint8 -> (even, odd) decoded fp32 planes, each (R, C).

    Row r of `even` is K-position 2r; `odd` is 2r+1 when pairs run along the
    first axis (weights). For activations packed along the last axis the
    same planes correspond to columns 2c / 2c+1.
    """
    if normal_dtype == "int8":
        raise ValueError("int8 codes are not nibble-packed; split the code "
                         "planes and use decode_pair_planes directly")
    # widen before shifting: Mosaic has no logical shift on uint8 vectors
    p = packed.astype(jnp.int32)
    return decode_pair_planes(p >> 4, p & 0xF, normal_dtype, spec)


# --------------------------------------------------------------------------
# In-kernel OVP fake quantization (the fused activation prologue).
# Value-domain mirror of encode->decode: identical outlier/victim selection
# (Algorithm 1) and identical rounding, so the fused path is bit-compatible
# with the XLA encode -> kernel decode round trip it replaces.
# --------------------------------------------------------------------------
def _roundtrip_normal(u: jax.Array, normal_dtype: str) -> jax.Array:
    if normal_dtype == "int4":
        return jnp.clip(jnp.round(u), -7, 7)
    if normal_dtype == "int8":
        return jnp.clip(jnp.round(u), -127, 127)
    # flint4: nearest magnitude in {0,1,2,3,4,6,8,16} via midpoint
    # thresholds (ties -> smaller magnitude, matching flint4_encode's
    # argmin tie rule). A select chain, not a LUT gather: pallas_call
    # rejects captured constant arrays in the kernel body.
    a = jnp.abs(u)
    mag = jnp.where(a <= 0.5, 0.0,
          jnp.where(a <= 1.5, 1.0,
          jnp.where(a <= 2.5, 2.0,
          jnp.where(a <= 3.5, 3.0,
          jnp.where(a <= 5.0, 4.0,
          jnp.where(a <= 7.0, 6.0,
          jnp.where(a <= 12.0, 8.0, 16.0)))))))
    return jnp.where((u < 0) & (mag > 0), -mag, mag)


def _roundtrip_abfloat(u: jax.Array, spec: AbfloatSpec) -> jax.Array:
    return abfloat_decode(abfloat_encode(u, spec), spec)


def quantize_pair_planes(u0: jax.Array, u1: jax.Array, normal_dtype: str,
                         spec: AbfloatSpec):
    """Scaled value planes -> OVP fake-quantized planes (Algorithm 1).

    Same outlier-victim selection as `core.ovp.ovp_encode_codes`: per pair,
    at most one outlier survives as abfloat, its neighbour is pruned to 0.
    """
    t = float(NORMAL_MAX[normal_dtype])
    a0, a1 = jnp.abs(u0), jnp.abs(u1)
    o0, o1 = a0 > t, a1 > t
    first_out = o0 & (~o1 | (a0 >= a1))
    second_out = o1 & ~first_out
    q0 = jnp.where(first_out, _roundtrip_abfloat(u0, spec),
                   jnp.where(second_out, 0.0,
                             _roundtrip_normal(u0, normal_dtype)))
    q1 = jnp.where(second_out, _roundtrip_abfloat(u1, spec),
                   jnp.where(first_out, 0.0,
                             _roundtrip_normal(u1, normal_dtype)))
    return q0.astype(jnp.float32), q1.astype(jnp.float32)


# --------------------------------------------------------------------------
# Shared tile phases (2-D and grouped kernel bodies both use these)
# --------------------------------------------------------------------------
def _weight_tile_planes(wp: jax.Array, w_dtype: str, w_spec: AbfloatSpec):
    """(P, bk2, bn) weight tile -> (even, odd) decoded fp32 half-K planes:
    int8 code planes (P=2) or packed nibbles (P=1)."""
    if w_dtype == "int8":
        return decode_pair_planes(wp[0], wp[1], "int8", w_spec)
    return decode_nibble_planes(wp[0], w_dtype, w_spec)


def _act_tile_planes(a: jax.Array, sa: jax.Array, a_mode: str,
                     a_dtype: str, a_spec: AbfloatSpec):
    """Activation prologue: (P, bm, bk2) tile -> (even, odd) fp32 planes.

    codes4 decodes packed nibbles (P=1); codes8 decodes int8 code planes;
    quantize runs the in-kernel OVP fake-quant at the per-row scale `sa`;
    fp passes the planes through.
    """
    if a_mode == "codes4":
        return decode_nibble_planes(a[0], a_dtype, a_spec)
    if a_mode == "codes8":
        return decode_pair_planes(a[0], a[1], "int8", a_spec)
    a0, a1 = a[0].astype(jnp.float32), a[1].astype(jnp.float32)
    if a_mode == "quantize":
        return quantize_pair_planes(a0 / sa, a1 / sa, a_dtype, a_spec)
    return a0, a1  # fp


# --------------------------------------------------------------------------
# The unified fused kernel body
# --------------------------------------------------------------------------
def _fused_mm_kernel(a_ref, sa_ref, wp_ref, sw_ref, o_ref, *,
                     w_dtype: str, w_spec: AbfloatSpec,
                     a_mode: str, a_dtype: str, a_spec: AbfloatSpec):
    """One (batch, M, N, K) grid step.

    a_ref  (P, 1, bm, bk2)  even/odd planes (fp/quantize/codes8, P=2) or
                            packed nibbles (codes4, P=1)
    sa_ref (1, bm, 1)    per-row activation scale (1.0 when unscaled)
    wp_ref (P, bk2, bn)  packed nibbles (P=1) or int8 OVP code planes (P=2)
    sw_ref (1, bn)       per-output-channel weight scale (1.0 when unscaled)
    o_ref  (1, bm, bn)   fp32 accumulator; scales applied on the last K step
    """
    @pl.when(pl.program_id(3) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    w_even, w_odd = _weight_tile_planes(wp_ref[...], w_dtype, w_spec)
    a_even, a_odd = _act_tile_planes(a_ref[:, 0], sa_ref[0], a_mode,
                                     a_dtype, a_spec)

    o_ref[0] += (
        jnp.dot(a_even, w_even, preferred_element_type=jnp.float32)
        + jnp.dot(a_odd, w_odd, preferred_element_type=jnp.float32))

    # -- scale epilogue ---------------------------------------------------
    @pl.when(pl.program_id(3) == pl.num_programs(3) - 1)
    def _epilogue():
        o_ref[0] = o_ref[0] * sa_ref[0] * sw_ref[...]


def _act_tile_planes_static(a: jax.Array, a_dtype: str,
                            a_spec: AbfloatSpec, s: jax.Array):
    """Static-scale activation prologue: OVP fake-quant of the (2, bm, bk2)
    planes at the calibrated scalar `s`. One reciprocal per tile instead
    of a per-row divide, and no (bm, 1) scale tile is ever streamed."""
    r = 1.0 / s
    return quantize_pair_planes(a[0].astype(jnp.float32) * r,
                                a[1].astype(jnp.float32) * r, a_dtype, a_spec)


def _fused_mm_kernel_static(a_ref, sa_ref, wp_ref, sw_ref, o_ref, *,
                            w_dtype: str, w_spec: AbfloatSpec,
                            a_dtype: str, a_spec: AbfloatSpec):
    """Static-scale twin of `_fused_mm_kernel` (a_mode="quantize" only).

    The calibrated activation scale arrives as ONE (1, 1) scalar operand
    instead of the (B, M, 1) per-row stream: a single word replaces a
    whole operand plane, one compiled kernel serves every calibrated
    site/scale, and — upstream — no per-step 3σ std is ever computed.
    This is the serving fast path for `act_scale_mode="static"`.

    a_ref  (2, 1, bm, bk2)  fp planes, quantized in-kernel at the scalar
    sa_ref (1, 1)        the calibrated scale (same word on every tile)
    wp_ref (P, bk2, bn)  packed nibbles (P=1) or int8 OVP code planes (P=2)
    sw_ref (1, bn)       per-output-channel weight scale
    o_ref  (1, bm, bn)   fp32 accumulator; scales applied on the last K step
    """
    @pl.when(pl.program_id(3) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    s = sa_ref[0, 0]
    w_even, w_odd = _weight_tile_planes(wp_ref[...], w_dtype, w_spec)
    a_even, a_odd = _act_tile_planes_static(a_ref[:, 0], a_dtype, a_spec,
                                            s)

    o_ref[0] += (
        jnp.dot(a_even, w_even, preferred_element_type=jnp.float32)
        + jnp.dot(a_odd, w_odd, preferred_element_type=jnp.float32))

    @pl.when(pl.program_id(3) == pl.num_programs(3) - 1)
    def _epilogue():
        o_ref[0] = o_ref[0] * (s * sw_ref[...])


# --------------------------------------------------------------------------
# Grouped (per-expert) kernel body: one expert grid dim over stacked weights
# --------------------------------------------------------------------------
def _grouped_mm_kernel(a_ref, sa_ref, wp_ref, sw_ref, o_ref, *,
                       w_dtype: str, w_spec: AbfloatSpec,
                       a_mode: str, a_dtype: str, a_spec: AbfloatSpec):
    """One (batch, expert, M, N, K) grid step.

    The expert grid dim indexes the stacked weight's leading axis, so each
    (e, m, n) tile streams only expert e's packed bytes — no broadcast of
    the full (E, K, N) stack, no global coordination between experts
    (the paper's memory-alignment claim extends to the MoE layout).

    a_ref  (P, 1, 1, bm, bk2) one expert's dispatched-slot planes
    sa_ref (1, 1, bm, 1)      per-slot activation scale
    wp_ref (P, 1, bk2, bn)    this expert's packed weight tile (planes)
    sw_ref (1, 1, bn)         this expert's per-output-channel scale
    o_ref  (1, 1, bm, bn)     fp32 accumulator, scales on the last K step
    """
    @pl.when(pl.program_id(4) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    w_even, w_odd = _weight_tile_planes(wp_ref[:, 0], w_dtype, w_spec)
    a_even, a_odd = _act_tile_planes(a_ref[:, 0, 0], sa_ref[0, 0], a_mode,
                                     a_dtype, a_spec)

    o_ref[0, 0] += (
        jnp.dot(a_even, w_even, preferred_element_type=jnp.float32)
        + jnp.dot(a_odd, w_odd, preferred_element_type=jnp.float32))

    @pl.when(pl.program_id(4) == pl.num_programs(4) - 1)
    def _epilogue():
        o_ref[0, 0] = o_ref[0, 0] * sa_ref[0, 0] * sw_ref[0]


def _grouped_mm_kernel_static(a_ref, sa_ref, wp_ref, sw_ref, o_ref, *,
                              w_dtype: str, w_spec: AbfloatSpec,
                              a_dtype: str, a_spec: AbfloatSpec):
    """Static-scale twin of `_grouped_mm_kernel` (a_mode="quantize" only):
    same scalar-operand prologue/epilogue as `_fused_mm_kernel_static`,
    on the (batch, expert, M, N, K) grid.

    a_ref  (2, 1, 1, bm, bk2)  one expert's dispatched-slot fp planes
    sa_ref (1, 1, 1)       the calibrated scale (same word on every tile)
    wp_ref (P, 1, bk2, bn) this expert's packed weight tile (planes)
    sw_ref (1, 1, bn)      this expert's per-output-channel scale
    o_ref  (1, 1, bm, bn)  fp32 accumulator, scales on the last K step
    """
    @pl.when(pl.program_id(4) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    s = sa_ref[0, 0, 0]
    w_even, w_odd = _weight_tile_planes(wp_ref[:, 0], w_dtype, w_spec)
    a_even, a_odd = _act_tile_planes_static(a_ref[:, 0, 0], a_dtype,
                                            a_spec, s)

    o_ref[0, 0] += (
        jnp.dot(a_even, w_even, preferred_element_type=jnp.float32)
        + jnp.dot(a_odd, w_odd, preferred_element_type=jnp.float32))

    @pl.when(pl.program_id(4) == pl.num_programs(4) - 1)
    def _epilogue():
        o_ref[0, 0] = o_ref[0, 0] * (s * sw_ref[0])


# --------------------------------------------------------------------------
# pallas_call wrappers
# --------------------------------------------------------------------------
def _act_planes(a: jax.Array, a_mode: str) -> jax.Array:
    """(…, Ka) activation operand -> (P, …, K/2) plane stack: packed
    nibbles already hold a pair per byte (P=1); everything else splits
    into even/odd K planes (P=2) here, in XLA."""
    if a_mode == "codes4":
        return a[None]
    return jnp.stack([a[..., 0::2], a[..., 1::2]])


def _weight_planes(w: jax.Array, w_dtype: str) -> jax.Array:
    """(…, Kw, N) weight codes -> (P, …, K/2, N) plane stack (int8 codes
    split into even/odd K rows, P=2; packed nibbles P=1)."""
    if w_dtype == "int8":
        return jnp.stack([w[..., 0::2, :], w[..., 1::2, :]])
    return w[None]


def fused_ovp_matmul_kernel(a: jax.Array, a_scale: jax.Array,
                            w_data: jax.Array, w_scale: jax.Array, *,
                            w_dtype: str = "int4",
                            a_mode: str = "fp", a_dtype: str = "int4",
                            w_spec: AbfloatSpec | None = None,
                            a_spec: AbfloatSpec | None = None,
                            a_static: bool = False,
                            bm: int = 128, bn: int = 128, bk: int = 256,
                            interpret: bool = False) -> jax.Array:
    """a: (B, M, Ka); a_scale: (B, M, 1); w_data: (Kw, N); w_scale: (1, N).

    Ka is K for fp/quantize/codes8 activations and K/2 for codes4; Kw is
    K/2 for packed nibbles and K for int8 codes. Returns (B, M, N) fp32
    with both scales applied. Shapes must divide the (clamped) blocks —
    `repro.kernels.ops` owns padding.

    `a_static` (with a_mode="quantize") switches to the static prologue:
    `a_scale` is a single (1, 1) calibrated scalar instead of the
    (B, M, 1) per-row plane, and the kernel reads that one word — one
    compiled kernel serves every calibrated site/scale.
    """
    assert a_mode in ACT_MODES, a_mode
    w_spec = ABFLOAT_FOR_NORMAL[w_dtype] if w_spec is None else w_spec
    a_spec = ABFLOAT_FOR_NORMAL[a_dtype] if a_spec is None else a_spec

    b, m, _ = a.shape
    kw, n = w_data.shape
    k2 = kw if w_dtype != "int8" else kw // 2   # number of pairs along K
    bm, bn = min(bm, m), min(bn, n)
    bk2 = min(bk // 2, k2)
    grid = (b, m // bm, n // bn, k2 // bk2)

    assert k2 % bk2 == 0 and m % bm == 0 and n % bn == 0, \
        (a.shape, w_data.shape, (bm, bn, bk2))
    ap, wp = _act_planes(a, a_mode), _weight_planes(w_data, w_dtype)

    if a_static:
        assert a_mode == "quantize", \
            "static activation scales imply the in-kernel quantize prologue"
        assert a_scale.shape == (1, 1), a_scale.shape
        kernel = functools.partial(_fused_mm_kernel_static,
                                   w_dtype=w_dtype, w_spec=w_spec,
                                   a_dtype=a_dtype, a_spec=a_spec)
        sa_spec = pl.BlockSpec((1, 1), lambda bb, i, j, kk: (0, 0))
    else:
        kernel = functools.partial(_fused_mm_kernel, w_dtype=w_dtype,
                                   w_spec=w_spec, a_mode=a_mode,
                                   a_dtype=a_dtype, a_spec=a_spec)
        sa_spec = pl.BlockSpec((1, bm, 1), lambda bb, i, j, kk: (bb, i, 0))
    # `name` is the op's name in the device trace (`_fused_padded.<n>`, the
    # name of the jitted wrapper in kernels/ops.py that every served call
    # goes through, and the prefix perfbench's ovp_matmul_roofline finds)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((ap.shape[0], 1, bm, bk2),
                         lambda bb, i, j, kk: (0, bb, i, kk)),
            sa_spec,
            pl.BlockSpec((wp.shape[0], bk2, bn),
                         lambda bb, i, j, kk: (0, kk, j)),
            pl.BlockSpec((1, bn), lambda bb, i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn),
                               lambda bb, i, j, kk: (bb, i, j)),
        out_shape=jax.ShapeDtypeStruct((b, m, n), jnp.float32),
        interpret=interpret,
        name="_fused_padded",
    )(ap, a_scale, wp, w_scale)


# --------------------------------------------------------------------------
# Grouped pallas_call builder (stacked per-expert weights)
# --------------------------------------------------------------------------
def grouped_ovp_matmul_kernel(a: jax.Array, a_scale: jax.Array,
                              w_data: jax.Array, w_scale: jax.Array, *,
                              w_dtype: str = "int4",
                              a_mode: str = "fp", a_dtype: str = "int4",
                              w_spec: AbfloatSpec | None = None,
                              a_spec: AbfloatSpec | None = None,
                              a_static: bool = False,
                              bm: int = 128, bn: int = 128, bk: int = 256,
                              interpret: bool = False) -> jax.Array:
    """a: (B, E, M, Ka); a_scale: (B, E, M, 1); w_data: (E, Kw, N);
    w_scale: (E, 1, N). Returns (B, E, M, N) fp32 with both scales applied.

    The grid is (B, E, M/bm, N/bn, K2/bk2) with K innermost; the expert dim
    rides the grid like the batch dim, so per-expert MoE einsums hit one
    pallas_call with no XLA broadcast of the stacked weights. Shapes must
    divide the (clamped) blocks — `repro.kernels.ops` owns padding.

    `a_static` (with a_mode="quantize") takes the static prologue:
    `a_scale` is a single (1, 1, 1) calibrated scalar instead of the
    per-slot plane, exactly as in `fused_ovp_matmul_kernel`.
    """
    assert a_mode in ACT_MODES, a_mode
    w_spec = ABFLOAT_FOR_NORMAL[w_dtype] if w_spec is None else w_spec
    a_spec = ABFLOAT_FOR_NORMAL[a_dtype] if a_spec is None else a_spec

    b, e, m, _ = a.shape
    ew, kw, n = w_data.shape
    assert ew == e, (a.shape, w_data.shape)
    k2 = kw if w_dtype != "int8" else kw // 2   # number of pairs along K
    bm, bn = min(bm, m), min(bn, n)
    bk2 = min(bk // 2, k2)
    grid = (b, e, m // bm, n // bn, k2 // bk2)

    assert k2 % bk2 == 0 and m % bm == 0 and n % bn == 0, \
        (a.shape, w_data.shape, (bm, bn, bk2))
    ap, wp = _act_planes(a, a_mode), _weight_planes(w_data, w_dtype)

    if a_static:
        assert a_mode == "quantize", \
            "static activation scales imply the in-kernel quantize prologue"
        assert a_scale.shape == (1, 1, 1), a_scale.shape
        kernel = functools.partial(_grouped_mm_kernel_static,
                                   w_dtype=w_dtype, w_spec=w_spec,
                                   a_dtype=a_dtype, a_spec=a_spec)
        sa_spec = pl.BlockSpec((1, 1, 1),
                               lambda bb, ee, i, j, kk: (0, 0, 0))
    else:
        kernel = functools.partial(_grouped_mm_kernel, w_dtype=w_dtype,
                                   w_spec=w_spec, a_mode=a_mode,
                                   a_dtype=a_dtype, a_spec=a_spec)
        sa_spec = pl.BlockSpec((1, 1, bm, 1),
                               lambda bb, ee, i, j, kk: (bb, ee, i, 0))
    # `name` is the op's name in the device trace (`_grouped_padded.<n>`)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((ap.shape[0], 1, 1, bm, bk2),
                         lambda bb, ee, i, j, kk: (0, bb, ee, i, kk)),
            sa_spec,
            pl.BlockSpec((wp.shape[0], 1, bk2, bn),
                         lambda bb, ee, i, j, kk: (0, ee, kk, j)),
            pl.BlockSpec((1, 1, bn), lambda bb, ee, i, j, kk: (ee, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, bm, bn),
                               lambda bb, ee, i, j, kk: (bb, ee, i, j)),
        out_shape=jax.ShapeDtypeStruct((b, e, m, n), jnp.float32),
        interpret=interpret,
        name="_grouped_padded",
    )(ap, a_scale, wp, w_scale)


# --------------------------------------------------------------------------
# Back-compat 2-D builders (scaled-unit outputs, as the oracles in ref.py)
# --------------------------------------------------------------------------
def _ones_scales(b, m, n):
    return jnp.ones((b, m, 1), jnp.float32), jnp.ones((1, n), jnp.float32)


def ovp_matmul_w4a16(a: jax.Array, w_packed: jax.Array,
                     normal_dtype: str = "int4",
                     spec: AbfloatSpec | None = None,
                     bm: int = 128, bn: int = 128, bk: int = 256,
                     interpret: bool = False) -> jax.Array:
    """a: (M, K) fp; w_packed: (K/2, N) uint8 -> (M, N) fp32 (w-units)."""
    m, k = a.shape
    k2, n = w_packed.shape
    assert k == 2 * k2, (a.shape, w_packed.shape)
    sa, sw = _ones_scales(1, m, n)
    out = fused_ovp_matmul_kernel(a[None], sa, w_packed, sw,
                                  w_dtype=normal_dtype, a_mode="fp",
                                  w_spec=spec, bm=bm, bn=bn, bk=bk,
                                  interpret=interpret)
    return out[0]


def ovp_matmul_w4a4(a_packed: jax.Array, w_packed: jax.Array,
                    normal_dtype: str = "int4",
                    spec: AbfloatSpec | None = None,
                    bm: int = 128, bn: int = 128, bk: int = 256,
                    interpret: bool = False) -> jax.Array:
    """a_packed: (M, K/2) uint8; w_packed: (K/2, N) uint8 -> (M, N) fp32."""
    m, ak2 = a_packed.shape
    k2, n = w_packed.shape
    assert ak2 == k2, (a_packed.shape, w_packed.shape)
    sa, sw = _ones_scales(1, m, n)
    out = fused_ovp_matmul_kernel(a_packed[None], sa, w_packed, sw,
                                  w_dtype=normal_dtype, a_mode="codes4",
                                  a_dtype=normal_dtype, w_spec=spec,
                                  a_spec=spec, bm=bm, bn=bn, bk=bk,
                                  interpret=interpret)
    return out[0]
