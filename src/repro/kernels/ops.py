"""Jit'd public wrappers around the fused OVP Pallas kernel.

Handles: lhs normalization to the kernel's 3-D (batch, M, K) layout (every
lhs row in M, batch 1, for the shared 2-D weight), shape padding to block
multiples, scale broadcasting into the kernel's epilogue
layout (per-row activation / per-output-channel weight), QuantizedTensor
plumbing, and the interpret switch (CPU validation vs TPU execution).

Scales are applied *inside* the kernel epilogue — there is no post-kernel
XLA multiply; a quantized matmul is exactly one device dispatch. With a
calibrated `static_act_scale` the activation scale shrinks to a single
(1, 1) scalar operand — no per-row plane, no per-step scale computation
(see docs/calibration.md).
"""
from __future__ import annotations

import functools
from typing import Optional, Union

import jax
import jax.numpy as jnp

from repro.core.ovp import QuantizedTensor
from . import ovp_matmul as _mm
from . import ovp_encode as _enc


def _pad_to(x: jax.Array, mults, value=0):
    pads = []
    for d, m in zip(x.shape, mults):
        rem = (-d) % m
        pads.append((0, rem))
    if all(p == (0, 0) for p in pads):
        return x
    return jnp.pad(x, pads, constant_values=value)


@functools.partial(jax.jit, static_argnames=("w_dtype", "a_mode", "a_dtype",
                                             "a_static", "out_dtype",
                                             "interpret", "bm", "bn", "bk"))
def _fused_padded(a3: jax.Array, sa3: jax.Array,
                  w_data: jax.Array, sw: jax.Array, *, w_dtype: str,
                  a_mode: str, a_dtype: str, a_static: bool = False,
                  out_dtype=jnp.float32, interpret: bool = False,
                  bm: int = 128, bn: int = 128, bk: int = 256) -> jax.Array:
    """Pad operands to block multiples, run the fused kernel, slice back.

    a3 (B, M, Ka); sa3 (B, M, 1); w_data (Kw, N); sw (1, N).
    Padded activation rows get scale 1 (prologue divides by the scale) and
    padded codes/values decode to 0, so padding never perturbs the result.

    `a_static` takes the static-prologue kernel: sa3 is the calibrated
    (1, 1) scalar (a traced operand, so one jit entry and one compiled
    kernel serve every scale value) and never needs padding.
    """
    b, m, ka = a3.shape
    kw, n = w_data.shape
    k2 = kw if w_dtype != "int8" else kw // 2
    # clamp blocks to the problem, then pad up to the clamped multiples
    bm = min(bm, m)
    bn = min(bn, n)
    bk2 = min(bk // 2, k2)
    a_mult = bk2 if a_mode == "codes4" else 2 * bk2
    w_mult = bk2 if w_dtype != "int8" else 2 * bk2
    ap = _pad_to(a3, (1, bm, a_mult))
    sap = sa3 if a_static else _pad_to(sa3, (1, bm, 1), value=1.0)
    wp = _pad_to(w_data, (w_mult, bn))
    swp = _pad_to(sw, (1, bn), value=1.0)
    out = _mm.fused_ovp_matmul_kernel(ap, sap, wp, swp, w_dtype=w_dtype,
                                      a_mode=a_mode, a_dtype=a_dtype,
                                      a_static=a_static,
                                      bm=bm, bn=bn, bk=2 * bk2,
                                      interpret=interpret)
    return out[:, :m, :n].astype(out_dtype)


def _as_rows(x: jax.Array):
    """(…, M, K) -> ((1, R, K), rows) with every leading dim folded into M:
    R = prod(rows), rows = x.shape[:-1].

    The 2-D kernel's weight is one shared (Kw, N) that the batch grid dim
    does not index, so rows spread over that dim would each fetch and
    decode every weight tile again. Folded into M, a decode step's
    (slots, 1, K) rows form one M tile: each weight tile is fetched and
    decoded once per call."""
    if x.ndim < 2:
        raise ValueError(f"lhs must be at least 2-D, got {x.shape}")
    return x.reshape(1, -1, x.shape[-1]), x.shape[:-1]


def _as_4d(x: jax.Array):
    """(…, E, C, K) -> ((B, E, C, K), lead): leading dims fold into B."""
    if x.ndim < 3:
        raise ValueError(f"grouped lhs must be at least 3-D, got {x.shape}")
    lead = x.shape[:-3]
    b = 1
    for d in lead:
        b *= d
    return x.reshape(b, *x.shape[-3:]), lead


def _row_scale(s, x: jax.Array) -> jax.Array:
    """Broadcast a scalar / per-row scale to the kernel's (1, R, 1)."""
    s = jnp.asarray(s, jnp.float32)
    target = x.shape[:-1] + (1,)
    if s.ndim and s.shape == x.shape[:-1]:
        s = s[..., None]
    s3, _ = _as_rows(jnp.broadcast_to(s, target))
    return s3


def _col_scale(s, n: int) -> jax.Array:
    """Broadcast a scalar / per-channel weight scale to (1, N)."""
    s = jnp.asarray(s, jnp.float32)
    return jnp.broadcast_to(s.reshape(1, -1) if s.ndim else s, (1, n))


def fused_ovp_matmul(x: Union[jax.Array, QuantizedTensor],
                     w: QuantizedTensor, *,
                     a_dtype: Optional[str] = None,
                     act_scale: Optional[jax.Array] = None,
                     static_act_scale: Union[float, jax.Array, None] = None,
                     out_dtype=jnp.float32, interpret: bool = False,
                     bm: int = 128, bn: int = 128,
                     bk: int = 256) -> jax.Array:
    """Single-dispatch quantized matmul: (…, K) @ (K, N) -> (…, N).

    x is either a real-valued tensor — used as-is (W4A16/W8A16) or
    OVP-quantized in the kernel prologue when `a_dtype` is set (pass the
    per-tensor or per-row `act_scale`; no packed activation tensor is ever
    materialized) — or a pre-quantized `QuantizedTensor` whose codes are
    decoded in the prologue. Weight pairs must run along K; any leading lhs
    dims fold into M (`_as_rows`), so a 3-D decode-step GEMM is one
    (1, slots, K) call whose weight tiles are fetched once, as for 2-D.

    `static_act_scale` (the calibrated per-site scalar — a Python float
    or 0-d array) replaces `act_scale`: it reaches the kernel as a single
    (1, 1) scalar operand instead of the per-row plane, and no per-step
    scale computation of any kind runs. This is the
    `act_scale_mode="static"` serving fast path.
    """
    n = w.data.shape[-1]
    sw = _col_scale(w.scale, n)
    static = False
    if isinstance(x, QuantizedTensor):
        a_mode = "codes4" if x.is_packed else "codes8"
        a3, rows = _as_rows(x.data)
        sa3 = _row_scale(x.scale, x.data)
        a_dtype = x.normal_dtype
    elif a_dtype is not None:
        if static_act_scale is not None:
            a_mode = "quantize"
            a3, rows = _as_rows(x)
            sa3 = jnp.asarray(static_act_scale,
                              jnp.float32).reshape(1, 1)
            static = True
        elif act_scale is None:
            raise ValueError("in-kernel activation quantization needs an "
                             "act_scale (per-tensor or per-row) or a "
                             "static_act_scale constant")
        else:
            a_mode = "quantize"
            a3, rows = _as_rows(x)
            sa3 = _row_scale(act_scale, x)
    else:
        a_mode = "fp"
        a3, rows = _as_rows(x)
        sa3 = jnp.ones((a3.shape[0], a3.shape[1], 1), jnp.float32)
        a_dtype = w.normal_dtype
    out = _fused_padded(a3, sa3, w.data, sw, w_dtype=w.normal_dtype,
                        a_mode=a_mode, a_dtype=a_dtype, a_static=static,
                        out_dtype=out_dtype, interpret=interpret,
                        bm=bm, bn=bn, bk=bk)
    return out.reshape(*rows, n)


# --------------------------------------------------------------------------
# Grouped (per-expert) matmul over stacked weights
# --------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("w_dtype", "a_mode", "a_dtype",
                                             "a_static", "out_dtype",
                                             "interpret", "bm", "bn", "bk"))
def _grouped_padded(a4: jax.Array, sa4: jax.Array,
                    w_data: jax.Array, sw: jax.Array, *, w_dtype: str,
                    a_mode: str, a_dtype: str, a_static: bool = False,
                    out_dtype=jnp.float32,
                    interpret: bool = False, bm: int = 128, bn: int = 128,
                    bk: int = 256) -> jax.Array:
    """Pad grouped operands to block multiples, run the kernel, slice back.

    a4 (B, E, M, Ka); sa4 (B, E, M, 1); w_data (E, Kw, N); sw (E, 1, N).
    The expert dim never pads (block size 1 on the expert grid dim).
    `a_static` takes the static-prologue kernel (sa4 is the calibrated
    (1, 1, 1) scalar), exactly as in `_fused_padded`.
    """
    b, e, m, ka = a4.shape
    _, kw, n = w_data.shape
    k2 = kw if w_dtype != "int8" else kw // 2
    bm = min(bm, m)
    bn = min(bn, n)
    bk2 = min(bk // 2, k2)
    a_mult = bk2 if a_mode == "codes4" else 2 * bk2
    w_mult = bk2 if w_dtype != "int8" else 2 * bk2
    ap = _pad_to(a4, (1, 1, bm, a_mult))
    sap = sa4 if a_static else _pad_to(sa4, (1, 1, bm, 1), value=1.0)
    wp = _pad_to(w_data, (1, w_mult, bn))
    swp = _pad_to(sw, (1, 1, bn), value=1.0)
    out = _mm.grouped_ovp_matmul_kernel(ap, sap, wp, swp, w_dtype=w_dtype,
                                        a_mode=a_mode, a_dtype=a_dtype,
                                        a_static=a_static,
                                        bm=bm, bn=bn, bk=2 * bk2,
                                        interpret=interpret)
    return out[:, :, :m, :n].astype(out_dtype)


def _expert_row_scale(s, x: jax.Array) -> jax.Array:
    """Broadcast a scalar / per-slot act scale to the kernel's (B,E,M,1)."""
    s = jnp.asarray(s, jnp.float32)
    target = x.shape[:-1] + (1,)
    if s.ndim and s.shape == x.shape[:-1]:
        s = s[..., None]
    s4, _ = _as_4d(jnp.broadcast_to(s, target))
    return s4


def _expert_col_scale(s, e: int, n: int) -> jax.Array:
    """Broadcast per-expert weight scales to the kernel's (E, 1, N) layout.

    Accepts a scalar (shared), (E,) per-expert-tensor scales (vmapped
    tensor granularity), or (E, 1, N) per-expert-channel scales (vmapped
    channel granularity)."""
    s = jnp.asarray(s, jnp.float32)
    if s.ndim == 0:
        return jnp.broadcast_to(s, (e, 1, n))
    if s.ndim == 1:
        return jnp.broadcast_to(s[:, None, None], (e, 1, n))
    return jnp.broadcast_to(s.reshape(e, 1, -1), (e, 1, n))


def grouped_ovp_matmul(x: Union[jax.Array, QuantizedTensor],
                       w: QuantizedTensor, *,
                       a_dtype: Optional[str] = None,
                       act_scale: Optional[jax.Array] = None,
                       static_act_scale: Union[float, jax.Array,
                                               None] = None,
                       out_dtype=jnp.float32, interpret: bool = False,
                       bm: int = 128, bn: int = 128,
                       bk: int = 256) -> jax.Array:
    """Single-dispatch grouped matmul: (…, E, C, K) @ (E, K, N) -> (…, E, C, N).

    The per-expert mirror of `fused_ovp_matmul`: stacked packed weights ride
    an expert grid dim, per-expert scales apply in the accumulator epilogue,
    and the same activation modes are supported — fp lhs (weight-only, the
    MoE expert-einsum default), in-kernel OVP quantization when `a_dtype` +
    `act_scale` (or the constant `static_act_scale`) are set, or
    pre-quantized codes. Any dims left of (E, C, K) fold into the batch
    grid dim.
    """
    e, n = w.data.shape[0], w.data.shape[-1]
    sw = _expert_col_scale(w.scale, e, n)
    static = False
    if isinstance(x, QuantizedTensor):
        a_mode = "codes4" if x.is_packed else "codes8"
        a4, lead = _as_4d(x.data)
        sa4 = _expert_row_scale(x.scale, x.data)
        a_dtype = x.normal_dtype
    elif a_dtype is not None:
        if static_act_scale is not None:
            a_mode = "quantize"
            a4, lead = _as_4d(x)
            sa4 = jnp.asarray(static_act_scale,
                              jnp.float32).reshape(1, 1, 1)
            static = True
        elif act_scale is None:
            raise ValueError("in-kernel activation quantization needs an "
                             "act_scale (per-tensor or per-slot) or a "
                             "static_act_scale constant")
        else:
            a_mode = "quantize"
            a4, lead = _as_4d(x)
            sa4 = _expert_row_scale(act_scale, x)
    else:
        a_mode = "fp"
        a4, lead = _as_4d(x)
        sa4 = jnp.ones(a4.shape[:-1] + (1,), jnp.float32)
        a_dtype = w.normal_dtype
    out = _grouped_padded(a4, sa4, w.data, sw, w_dtype=w.normal_dtype,
                          a_mode=a_mode, a_dtype=a_dtype, a_static=static,
                          out_dtype=out_dtype, interpret=interpret,
                          bm=bm, bn=bn, bk=bk)
    return out.reshape(*lead, *out.shape[-3:]) if lead else out[0]


# --------------------------------------------------------------------------
# Shape-level wrappers (kernel benchmarks / tests drive these directly)
# --------------------------------------------------------------------------
def matmul_w4a16(a: jax.Array, w_data: jax.Array, w_scale: jax.Array,
                 normal_dtype: str = "int4", out_dtype=jnp.float32,
                 interpret: bool = False, bm: int = 128, bn: int = 128,
                 bk: int = 256) -> jax.Array:
    """a (M, K) fp @ packed w (K/2, N): decode + scales fused in-kernel."""
    m = a.shape[0]
    n = w_data.shape[1]
    return _fused_padded(a[None], jnp.ones((1, m, 1), jnp.float32),
                         w_data, _col_scale(w_scale, n),
                         w_dtype=normal_dtype, a_mode="fp",
                         a_dtype=normal_dtype, out_dtype=out_dtype,
                         interpret=interpret, bm=bm, bn=bn, bk=bk)[0]


def matmul_w4a4(a_data: jax.Array, a_scale: jax.Array, w_data: jax.Array,
                w_scale: jax.Array, normal_dtype: str = "int4",
                out_dtype=jnp.float32, interpret: bool = False,
                bm: int = 128, bn: int = 128, bk: int = 256) -> jax.Array:
    """packed a (M, K/2) @ packed w (K/2, N): decode + scales in-kernel."""
    n = w_data.shape[1]
    return _fused_padded(a_data[None], _row_scale(a_scale, a_data),
                         w_data, _col_scale(w_scale, n),
                         w_dtype=normal_dtype, a_mode="codes4",
                         a_dtype=normal_dtype, out_dtype=out_dtype,
                         interpret=interpret, bm=bm, bn=bn, bk=bk)[0]


def matmul_w8a8(a_data: jax.Array, a_scale: jax.Array, w_data: jax.Array,
                w_scale: jax.Array, out_dtype=jnp.float32,
                interpret: bool = False, bm: int = 128, bn: int = 128,
                bk: int = 256) -> jax.Array:
    """int8 OVP codes a (M, K) @ w codes (K, N), one code per byte."""
    n = w_data.shape[1]
    return _fused_padded(a_data[None], _row_scale(a_scale, a_data),
                         w_data, _col_scale(w_scale, n),
                         w_dtype="int8", a_mode="codes8", a_dtype="int8",
                         out_dtype=out_dtype, interpret=interpret,
                         bm=bm, bn=bn, bk=bk)[0]


def ovp_matmul(a: Union[jax.Array, QuantizedTensor], w: QuantizedTensor,
               out_dtype=jnp.float32, interpret: bool = False) -> jax.Array:
    """Public entry: dispatch from operand types (4-bit packed or int8 OVP).

    Leading batch dims of `a` fold into the kernel's M. Weight pairs must
    run along K (pair_axis == 0 of the 2-D weight).
    """
    return fused_ovp_matmul(a, w, out_dtype=out_dtype, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("normal_dtype", "interpret",
                                             "bm", "bk"))
def ovp_encode(x: jax.Array, scale: jax.Array, normal_dtype: str = "int4",
               interpret: bool = False, bm: int = 256,
               bk: int = 512) -> jax.Array:
    """x (M, K) real values -> packed OVP bytes (M, K/2) at `scale`.

    Standalone encoder (KV-cache packing, tests). The serving matmul path
    does NOT use it — activation quantization runs in the fused matmul
    prologue instead.
    """
    m, k = x.shape
    u = x.astype(jnp.float32) / scale
    bm_, bk_ = min(bm, m), min(bk, k)
    up = _pad_to(u, (bm_, bk_))
    out = _enc.ovp_encode_pallas(up, normal_dtype, bm=bm_, bk=bk_,
                                 interpret=interpret)
    return out[:m, :k // 2]
