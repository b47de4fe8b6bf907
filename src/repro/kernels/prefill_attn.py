"""Fused cache-write prefill kernel: OVP quantize-and-page + blockwise
causal attention in ONE `pallas_call` per cache site.

The slab engine prefills through a round trip this kernel deletes: run
blockwise attention over the prompt, quantize K/V with an XLA
encode/pack dispatch, `cache_write` into a fresh single-row cache, then
`_splice_slot` copies that row into the batched slab — the prompt's K/V
crosses HBM four times before the first decode step. Here the paged
engine hands the kernel the request's raw K/V *stage* and its block-table
row, and one kernel both:

  writes  — every stage tile quantizes IN-KERNEL (the same per-(token,
            head) 3σ scale + Algorithm-1 encode as `_quant_kv_token`,
            so paged bytes are bit-identical to slab bytes) and lands on
            its physical page through the block table (scalar-prefetch
            output index map; the pool is input/output-aliased so
            untouched pages keep their contents).
  attends — blockwise causal attention of the chunk's queries over the
            RAW stage values (exactly what the slab path attends), with
            online-softmax accumulation per stage tile.

CHUNKED PREFILL semantics: the stage `(1, S, Hkv, D)` holds the raw K/V
of every token of this request prefilled SO FAR (the engine appends each
chunk before the call). The kernel re-quantizes and rewrites history
pages on every chunk — quantization is deterministic per token row, so
the rewrite is byte-idempotent, and uniform tiles keep one trace per
stage length serving every chunk index (the chunk offset arrives as a
traced operand, only in the causal mask). Attention reads the raw stage,
not the quantized pages, so chunked prefill is mathematically the
standard causal forward computed in pieces — chunk boundaries never
inject quantization noise the slab path doesn't have.

`xla_prefill_attention` is the dense twin every backend can serve
(masked einsum + whole-stage quantize + page scatter): bit-identical
page bytes, attention equal up to softmax reassociation. Dispatch picks
between them via `backends.prefill_attention` (decline codes in
`prefill_decline_reason`; see docs/kv_cache.md).
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .decode_attn import (NEG_INF, _QK, finish, from_planes, heads_major,
                          init_carry, online_softmax_step, to_planes)
from .ovp_encode import encode_pair_planes, pack_pair_planes

STAGE_KEYS = ("stage_k", "stage_v")


def is_paged_prefill(cache) -> bool:
    return cache is not None and "block_table" in cache \
        and "stage_k" in cache


def prefill_decline_reason(q: jax.Array, cache) -> Optional[str]:
    """None when the fused prefill kernel serves this (q, cache) layout.

    The fused path exists for PAGED caches (slab prefill keeps the
    blockwise-attention + splice pipeline); codes are registered in
    `backends/base.py::DECLINE_CODES` and validated on return."""
    from repro.kernels.decode_attn import _registered
    return _registered(_prefill_decline_reason(q, cache))


def _prefill_decline_reason(q: jax.Array, cache) -> Optional[str]:
    if cache is None or "block_table" not in cache:
        return "prefill_not_paged"
    if "stage_k" not in cache or "stage_v" not in cache:
        return "prefill_no_stage"
    if q.shape[0] != 1:
        return "prefill_batch_gt_1"
    pool = cache.get("k", cache.get("k_data"))
    if pool is None:
        return "paged_no_pool"
    ps = pool.shape[1]
    if ps < 2 or ps % 2:
        return "paged_page_misaligned"
    s = cache["stage_k"].shape[1]
    if s % ps or cache["block_table"].shape[1] < s // ps:
        # stage must tile exactly onto pages and the table must back
        # every stage tile with a physical page
        return "prefill_stage_misaligned"
    if "k" in cache and cache["k"].shape[-1] % 2:
        return "decode_head_dim_odd"
    return None


# --------------------------------------------------------------------------
# Kernel bodies: grid (1, n_stage_tiles), kv-tile dim innermost. Blocks
# carry every kv head (the (8, 128) block rule; see decode_attn.py), and
# the chunk's queries ride as (Hkv, G*C, D) rows, row = g*C + c.
# --------------------------------------------------------------------------
def _quant_tile(xt):
    """(ps, Hkv, D) raw f32 tile in plane layout -> (packed (ps, Hkv, D/2)
    u8, scale (ps, Hkv) f32). Same arithmetic as layers._quant_kv_token
    (the std sums the lanes in another order, so scales agree to 1 ULP
    and the nibbles bit for bit)."""
    s = jnp.maximum(3.0 * jnp.std(xt, axis=-1) / 7.0, 1e-6)
    u = xt / s[..., None]
    d2 = xt.shape[-1] // 2
    return pack_pair_planes(*encode_pair_planes(u[..., :d2],
                                                u[..., d2:])), s


def _attend_tile(off_ref, q_ref, kt, vt, o_ref, m_ref, l_ref, *, ps: int,
                 c: int):
    """One online-softmax step of the chunk queries against one raw
    stage tile, causal on absolute positions (qpos = off + row % C)."""
    init_carry(o_ref, m_ref, l_ref)
    r = q_ref.shape[2]
    kpos = pl.program_id(1) * ps + jax.lax.broadcasted_iota(
        jnp.int32, (1, 1, ps), 2)
    qpos = off_ref[0] + jax.lax.broadcasted_iota(jnp.int32, (1, r, 1), 1) % c
    s = jax.lax.dot_general(q_ref[0], heads_major(kt), _QK,
                            preferred_element_type=jnp.float32)
    online_softmax_step(jnp.where(kpos <= qpos, s, NEG_INF),
                        heads_major(vt), None, o_ref, m_ref, l_ref)
    finish(o_ref, l_ref)


def _prefill_kernel_packed(tbl_ref, off_ref, q_ref, ksg_ref, vsg_ref,
                           kdp_ref, vdp_ref, ksp_ref, vsp_ref,
                           o_ref, m_ref, l_ref,
                           kd_ref, vd_ref, ks_ref, vs_ref, *, ps: int,
                           c: int):
    """q (1,Hkv,G*C,D) pre-scaled; ksg/vsg (1,ps,Hkv,D) raw stage tiles,
    both in plane layout; kd/vd/ks/vs out blocks land on page
    tbl[0, tile] (aliased pool)."""
    kt = ksg_ref[0].astype(jnp.float32)
    vt = vsg_ref[0].astype(jnp.float32)
    kd_ref[0], ks_ref[0] = _quant_tile(kt)
    vd_ref[0], vs_ref[0] = _quant_tile(vt)
    _attend_tile(off_ref, q_ref, kt, vt, o_ref, m_ref, l_ref, ps=ps, c=c)


def _prefill_kernel_fp(tbl_ref, off_ref, q_ref, ksg_ref, vsg_ref,
                       kp_ref, vp_ref, o_ref, m_ref, l_ref,
                       k_ref, v_ref, *, ps: int, c: int):
    kt = ksg_ref[0].astype(jnp.float32)
    vt = vsg_ref[0].astype(jnp.float32)
    k_ref[0] = kt.astype(k_ref.dtype)
    v_ref[0] = vt.astype(v_ref.dtype)
    _attend_tile(off_ref, q_ref, kt, vt, o_ref, m_ref, l_ref, ps=ps, c=c)


# --------------------------------------------------------------------------
# pallas_call builder + public wrappers
# --------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("packed", "ps", "n_tiles",
                                             "c", "interpret"))
def _prefill_call(bt, off, q4, ksg, vsg, pools, *, packed: bool, ps: int,
                  n_tiles: int, c: int, interpret: bool):
    """q4 (1, Hkv, G*C, D) f32 pre-scaled; ksg/vsg (1, S, Hkv, D) raw
    stage; off (1,) chunk offset; pools the pool leaves (aliased through
    to the outputs). Returns (out4, new_pools)."""
    _, hkv, r, d = q4.shape
    grid = (1, n_tiles)
    q_spec = pl.BlockSpec((1, hkv, r, d), lambda i, ss, *_: (0, 0, 0, 0))
    stage_spec = pl.BlockSpec((1, ps, hkv, d),
                              lambda i, ss, *_: (0, ss, 0, 0))
    carry_spec = pl.BlockSpec((1, hkv, r, 1), lambda i, ss, *_: (0, 0, 0, 0))
    page_spec = pl.BlockSpec((1, ps, hkv, pools[0].shape[-1]),
                             lambda i, ss, tbl, _: (tbl[0, ss], 0, 0, 0))
    scl_spec = pl.BlockSpec((1, ps, hkv),
                            lambda i, ss, tbl, _: (tbl[0, ss], 0, 0))
    carry_shape = jax.ShapeDtypeStruct((1, hkv, r, 1), jnp.float32)
    o_shape = jax.ShapeDtypeStruct((1, hkv, r, d), jnp.float32)
    pool_shapes = tuple(jax.ShapeDtypeStruct(p.shape, p.dtype)
                        for p in pools)
    pool_specs = tuple(scl_spec if p.ndim == 3 else page_spec
                       for p in pools)
    kernel = functools.partial(
        _prefill_kernel_packed if packed else _prefill_kernel_fp, ps=ps, c=c)
    # pool operands sit after (bt, off, q4, ksg, vsg); their outputs
    # after (o, m, l) — aliasing keeps pages no stage tile touches intact
    aliases = {5 + i: 3 + i for i in range(len(pools))}
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=grid,
        in_specs=[q_spec, stage_spec, stage_spec, *pool_specs],
        out_specs=(q_spec, carry_spec, carry_spec, *pool_specs))
    # `name` is the op's name in the device trace (`_prefill_call.<n>`)
    res = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=(o_shape, carry_shape, carry_shape, *pool_shapes),
        input_output_aliases=aliases,
        interpret=interpret, name="_prefill_call")(
            bt, off, q4, ksg, vsg, *pools)
    return res[0], res[3:]


def fused_prefill_attention(q: jax.Array, cache, positions: jax.Array, *,
                            interpret: bool = False) -> Tuple[jax.Array, dict]:
    """One pallas_call: causal attention of the chunk over the raw stage
    + OVP quantize-and-write of every stage tile onto its physical page.

    q: (1, C, H, D) chunk queries (rope applied); `cache` a paged cache
    dict carrying pool leaves, a single-row "block_table" (1, n), and the
    raw "stage_k"/"stage_v" (1, S, Hkv, D) with the current chunk already
    appended; positions: (1, C) absolute positions of the chunk (the
    offset positions[0, 0] is traced — one trace per stage length serves
    every chunk index). Returns (out (1, C, H, D), new cache dict with
    updated pool leaves). Layout preconditions are
    `prefill_decline_reason`'s job — callers go through
    `backends.prefill_attention`.

    A packed cache encodes pairs of adjacent lanes, so the stage and the
    queries enter in the even/odd plane layout (`to_planes`) and the
    output leaves through `from_planes`; fp caches stay in natural order.
    """
    b, c, h, d = q.shape
    packed = "k_data" in cache
    stage_k = cache["stage_k"].astype(jnp.float32)
    stage_v = cache["stage_v"].astype(jnp.float32)
    s, hkv = stage_k.shape[1], stage_k.shape[2]
    pool_keys = ("k_data", "v_data", "k_scl", "v_scl") if packed \
        else ("k", "v")
    pools = tuple(cache[key] for key in pool_keys)
    ps = pools[0].shape[1]
    g = h // hkv
    q4 = q.reshape(b, c, hkv, g, d).transpose(0, 2, 3, 1, 4) \
        .reshape(b, hkv, g * c, d).astype(jnp.float32) / math.sqrt(d)
    if packed:
        q4, stage_k, stage_v = (to_planes(x) for x in (q4, stage_k, stage_v))
    bt = cache["block_table"].astype(jnp.int32)
    off = positions[0, :1].astype(jnp.int32)
    out4, new_pools = _prefill_call(
        bt, off, q4, stage_k, stage_v, pools, packed=packed, ps=ps,
        n_tiles=s // ps, c=c, interpret=interpret)
    if packed:
        out4 = from_planes(out4)
    out = out4.reshape(b, hkv, g, c, d).transpose(0, 3, 1, 2, 4) \
        .reshape(b, c, h, d).astype(q.dtype)
    new_cache = dict(cache)
    for key, pool in zip(pool_keys, new_pools):
        new_cache[key] = pool
    return out, new_cache


# --------------------------------------------------------------------------
# Dense twin (any backend; also the decline fallback)
# --------------------------------------------------------------------------
def xla_prefill_attention(q: jax.Array, cache,
                          positions: jax.Array) -> Tuple[jax.Array, dict]:
    """Masked-einsum attention over the raw stage + whole-stage quantize
    + page scatter. Page bytes are bit-identical to the fused kernel's
    (same per-token quantization arithmetic); the attention output agrees
    up to softmax reassociation."""
    from repro.models.layers import _quant_kv_token
    b, c, h, d = q.shape
    stage_k, stage_v = cache["stage_k"], cache["stage_v"]
    s, hkv = stage_k.shape[1], stage_k.shape[2]
    g = h // hkv
    k = stage_k.astype(jnp.float32)
    v = stage_v.astype(jnp.float32)
    qg = q.reshape(b, c, hkv, g, d).astype(jnp.float32) / math.sqrt(d)
    scores = jnp.einsum("bqhgd,bshd->bhgqs", qg, k,
                        preferred_element_type=jnp.float32)
    valid = jnp.arange(s)[None, None, :] <= positions[:, :, None]
    scores = jnp.where(valid[:, None, None], scores, NEG_INF)
    p_att = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqs,bshd->bqhgd", p_att, v,
                     preferred_element_type=jnp.float32)
    out = out.reshape(b, c, h, d).astype(q.dtype)

    new_cache = dict(cache)
    bt = cache["block_table"]
    packed = "k_data" in cache
    ps = (cache["k_data"] if packed else cache["k"]).shape[1]
    n_tiles = s // ps
    pages = bt[:, :n_tiles].reshape(-1)

    def scatter(pool, vals):
        tiles = vals.reshape((b * n_tiles, ps) + vals.shape[2:])
        return pool.at[pages].set(tiles.astype(pool.dtype))

    if packed:
        kd, ks = _quant_kv_token(stage_k)
        vd, vs = _quant_kv_token(stage_v)
        for key, vals in (("k_data", kd), ("v_data", vd),
                          ("k_scl", ks), ("v_scl", vs)):
            new_cache[key] = scatter(cache[key], vals)
    else:
        new_cache["k"] = scatter(cache["k"], stage_k)
        new_cache["v"] = scatter(cache["v"], stage_v)
    return out, new_cache
