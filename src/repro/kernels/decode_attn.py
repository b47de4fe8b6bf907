"""Fused decode-attention Pallas kernel over (optionally OVP-packed) KV
caches — the serving decode path that makes the 4-bit cache pay for itself.

The problem it fixes: the seed decode path dequantized the ENTIRE packed
(B, max_len, Hkv, D) cache to bf16 every step, for every layer, before
attention ran as a plain XLA einsum — rematerializing exactly the dense
tensor the 4-bit cache was supposed to eliminate (the decode HBM term came
back, plus a full-cache decode dispatch per layer per token).

This kernel reads the packed `k_data`/`v_data` nibbles and the
per-(token, head) scales straight from HBM and unpacks/dequantizes PER KV
TILE in VMEM, inside the same kernel that consumes them:

  grid      — (batch, S/bs) with the kv-tile dim innermost, so the
              row's (Hkv, G, D) output block stays resident in VMEM while
              tiles of all its kv heads stream through; one `pallas_call`
              per layer per step.
  prologue  — a packed tile decodes branch-free on the VPU (same
              nibble-plane trick as `ovp_matmul`: even K-lanes in the high
              nibbles, odd in the low, so no interleaving relayout is ever
              needed); fp16/bf16 caches take the same kernel minus the
              unpack phase, in natural lane order.
  body      — online-softmax accumulation in f32: scores fold the
              per-token K scale in (s = (q @ k_codes^T) * k_scl), the
              probabilities fold the V scale (p * v_scl) so decoded code
              planes feed the MXU directly.
  masking   — length / ring / sliding-window validity is computed
              IN-KERNEL from the traced `pos`, so ONE compiled kernel
              serves every active-length mix in the batch (continuous
              batching never retraces on request churn).
  epilogue  — the accumulator normalizes by the softmax denominator on
              the last tile.

HBM read per decode step for the packed path drops ~4x vs the dequant
path (1 byte per 2 values + one f32 scale per (token, head) vs 2-4 bytes
per value), and the full-cache dequant materialization disappears.

For packed caches the queries enter, and the outputs leave, in the
even/odd plane layout (first D/2 lanes = even K-lanes); the public wrapper
permutes once on the (B, 1, H, D) operands.

PAGED CACHES (serve/paging.py): a paged cache stores its K/V data as a
global `(n_pages, page_size, Hkv, …)` pool plus a per-row block table
`(B, pages_per_row)` int32 mapping logical page j (token rows
[j*page_size, (j+1)*page_size)) to a physical page. Because this kernel
already streams one kv tile per grid step, paging is ONE INDIRECTION on
the kv-tile grid dim: the block table rides in as a scalar-prefetch
operand and the kv BlockSpec index map reads `table[b, ss]` instead of
`ss` — page size == kv tile size, so each gather is a whole tile and the
kernel bodies (unpack, scores, online softmax, masking) are shared
verbatim with the slab path. Logical slot arithmetic is unchanged
(`program_id(2) * page_size + iota`), so length/ring/window masking and
bit-for-bit equivalence with the slab kernel at `block_s == page_size`
come for free.

`xla_decode_attention` below is the dense fallback (full-cache dequant +
einsum) that non-kernel backends serve and declined layouts fall back to
— for paged caches it first materializes the pages into a slab
(`gather_paged_cache`), so every backend serves bit-identical results;
`models/layers.py::decode_attention` routes between them through the
backend registry (see docs/kv_cache.md for the decline vocabulary).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.datatypes import ABFLOAT_FOR_NORMAL
from repro.core.ovp import ovp_decode_codes, unpack4
from .ovp_matmul import decode_nibble_planes

NEG_INF = -1e30

# KV dtype the packed cache encodes with (see layers._quant_kv_token)
KV_NORMAL_DTYPE = "int4"


# --------------------------------------------------------------------------
# Dense (XLA) path: full-cache dequant + einsum. This is the fallback the
# paper's critics describe — kept as the reference and the decline target.
# --------------------------------------------------------------------------
def dequant_kv(data: jax.Array, scl: jax.Array) -> jax.Array:
    """Packed (…, T, Hkv, D/2) nibbles + (…, T, Hkv) scales -> f32 values.

    This materializes the WHOLE dense tensor — fine for tests and the XLA
    fallback, but never traced in a fused-kernel decode step (the
    zero-dequant acceptance test asserts exactly that)."""
    vals = ovp_decode_codes(unpack4(data, -1), KV_NORMAL_DTYPE, pair_axis=-1)
    return vals * scl[..., None]


def gather_paged_cache(cache):
    """Materialize a paged cache into a `(B, pages_per_row * page_size,
    …)` slab dict — the dense fallback's view of the pool.

    One `jnp.take` per leaf through the block table; this is exactly the
    per-step HBM rematerialization the paged kernel avoids, kept so
    non-kernel backends serve bit-identical results on paged caches."""
    bt = cache["block_table"]                       # (B, pages_per_row)
    b, n = bt.shape
    out = {}
    for key in ("k", "v", "k_data", "v_data", "k_scl", "v_scl"):
        if key in cache:
            pool = cache[key]                       # (P, ps, …)
            flat = jnp.take(pool, bt.reshape(-1), axis=0)
            out[key] = flat.reshape((b, n * pool.shape[1]) + pool.shape[2:])
    return out


def read_cache_dense(cache, dtype=None):
    """(k, v) dense views of a KV cache dict (fp or OVP-packed; paged
    caches materialize through the block table first).

    dtype=None keeps fp caches in their native dtype; packed caches decode
    to bf16 (matching the seed `cache_read` contract)."""
    if "block_table" in cache:
        cache = gather_paged_cache(cache)
    if "k" in cache:
        k, v = cache["k"], cache["v"]
        if dtype is None:
            return k, v
        return k.astype(dtype), v.astype(dtype)
    kd = dequant_kv(cache["k_data"], cache["k_scl"])
    vd = dequant_kv(cache["v_data"], cache["v_scl"])
    if dtype is None:
        dtype = jnp.bfloat16
    return kd.astype(dtype), vd.astype(dtype)


def slot_validity(pos: jax.Array, slots: jax.Array, *, window: int,
                  ring: int):
    """(abs_pos, valid) for cache slots given per-row `pos` (B,).

    Shared by the dense path and the tests; the kernel computes the same
    arithmetic on its per-tile iota. `ring` > 0 means slot i holds the
    largest p' <= pos with p' % ring == i; otherwise slot i is position i.
    """
    p = pos[:, None]
    if ring:
        abs_pos = p - ((p - slots[None, :]) % ring)
        valid = abs_pos >= 0
    else:
        abs_pos = jnp.broadcast_to(slots[None, :],
                                   (pos.shape[0], slots.shape[0]))
        valid = abs_pos <= p
    if window:
        valid = valid & (abs_pos > p - window) & (abs_pos <= p)
    return abs_pos, valid


def xla_decode_attention(q: jax.Array, cache, pos: jax.Array, *,
                         window: int = 0, ring: int = 0) -> jax.Array:
    """Single-token attention over a cache, dense XLA path.

    q: (B, 1, H, D); pos: (B,) current absolute position (token at `pos`
    already written). Dequantizes the whole cache first — the decode HBM
    term the fused kernel exists to remove. Paged caches materialize into
    a slab through the block table (and trim to the ring length: the pool
    rounds a ring up to whole pages, and the modular slot arithmetic must
    never see the rounding tail).
    """
    if "block_table" in cache:
        cache = gather_paged_cache(cache)
        if ring:
            cache = {key: leaf[:, :ring] for key, leaf in cache.items()}
    k, v = read_cache_dense(cache, dtype=None)
    b, s_len, hkv, d = k.shape
    h = q.shape[2]
    g = h // hkv
    scale = 1.0 / math.sqrt(d)
    qg = q.reshape(b, 1, hkv, g, d)
    s = jnp.einsum("bqhgd,bshd->bhgqs", qg.astype(k.dtype), k,
                   preferred_element_type=jnp.float32) * scale
    _, valid = slot_validity(pos, jnp.arange(s_len), window=window,
                             ring=ring)
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    p_att = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqs,bshd->bqhgd", p_att.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, h, d).astype(q.dtype)


# --------------------------------------------------------------------------
# Decline vocabulary (machine-readable; recorded in dispatch_stats())
# --------------------------------------------------------------------------
def decline_reason(q: jax.Array, cache) -> Optional[str]:
    """None when the fused kernel can serve this (q, cache) layout; codes
    are registered in `backends/base.py::DECLINE_CODES["decode_attn"]`
    (validated by `_registered` below and re-checked at the backend
    boundary by `decline()`)."""
    return _registered(_decline_reason(q, cache))


def _registered(code: Optional[str]) -> Optional[str]:
    # lazy import: backends imports this module at registry construction,
    # so a module-level `from repro.backends.base import decline` would
    # cycle; by the first dispatch the registry is fully imported
    from repro.backends.base import decline
    return decline(code)


def _decline_reason(q: jax.Array, cache) -> Optional[str]:
    if q.shape[1] != 1:
        return "decode_q_tokens_gt_1"
    paged = "block_table" in cache
    leaf = cache.get("k", cache.get("k_data"))
    if leaf is None:
        # a table with no pool behind it is malformed paging, not a
        # missing cache — the distinct code routes the caller to the
        # pool construction, not the cache construction
        return "paged_no_pool" if paged else "decode_no_kv_cache"
    if paged:
        bt = cache["block_table"]
        if bt.ndim != 2 or not jnp.issubdtype(bt.dtype, jnp.integer):
            return "paged_table_rank"
        if leaf.shape[0] == 0 or bt.shape[1] == 0:
            return "decode_empty_cache"
        if leaf.shape[1] < 2 or leaf.shape[1] % 2 != 0:
            # page size IS the kv tile size; odd tiles break the even/odd
            # lane tiling the TPU layouts want (PagePoolCfg enforces the
            # same invariant at pool construction)
            return "paged_page_misaligned"
    elif leaf.shape[1] == 0:
        return "decode_empty_cache"
    if "k" in cache and cache["k"].shape[-1] % 2 != 0:
        # the shared even/odd-plane body needs an even head_dim (packed
        # caches are guaranteed even at construction)
        return "decode_head_dim_odd"
    return None


# --------------------------------------------------------------------------
# Kernel bodies. A block carries every kv head of its rows: a TPU block's
# last two dims must be (8, 128)-aligned or whole, and the cache's trailing
# (Hkv, D) dims are whole only together. In VMEM a tile is turned
# head-major, (bs, Hkv, D) -> (Hkv, bs, D), so both attention products are
# head-batched matmuls with the batch dim leading, as Mosaic wants.
# --------------------------------------------------------------------------
_QK = (((2,), (2,)), ((0,), (0,)))   # (Hkv,R,D) x (Hkv,bs,D) -> (Hkv,R,bs)
_PV = (((2,), (1,)), ((0,), (0,)))   # (Hkv,R,bs) x (Hkv,bs,D) -> (Hkv,R,D)


def heads_major(x: jax.Array) -> jax.Array:
    """(bs, Hkv, X) tile -> (Hkv, bs, X)."""
    return jnp.swapaxes(x, 0, 1)


def decode_kv_tile(packed: jax.Array) -> jax.Array:
    """(bs, Hkv, D/2) packed nibbles -> (Hkv, bs, D) f32 codes in plane
    layout (even K-lanes in [..., :D/2], odd in [..., D/2:])."""
    spec = ABFLOAT_FOR_NORMAL[KV_NORMAL_DTYPE]
    even, odd = decode_nibble_planes(packed, KV_NORMAL_DTYPE, spec)
    return heads_major(jnp.concatenate([even, odd], axis=-1))


def online_softmax_step(s, v, v_scl, o_ref, m_ref, l_ref):
    """One kv-tile online-softmax update of the resident (Hkv, R, D)
    output block. s: (Hkv, R, bs) masked scores; v: (Hkv, bs, D) values;
    v_scl: (Hkv, 1, bs) per-token V scale folded into the probabilities,
    or None (fp caches)."""
    m_prev = m_ref[0]                                      # (Hkv, R, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)                                 # (Hkv, R, bs)
    corr = jnp.exp(m_prev - m_new)
    l_ref[0] = l_ref[0] * corr + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[0] = m_new
    if v_scl is not None:
        p = p * v_scl
    o_ref[0] = o_ref[0] * corr + jax.lax.dot_general(
        p, v, _PV, preferred_element_type=jnp.float32)


def init_carry(o_ref, m_ref, l_ref):
    """Zero the output block and reset the running max / denominator on
    the first kv tile (grid dim 1 in every attention kernel)."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)


def finish(o_ref, l_ref):
    """Normalize by the softmax denominator after the last kv tile."""
    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _norm():
        o_ref[0] = o_ref[0] / jnp.maximum(l_ref[0], 1e-30)


def _tile_mask(pos, bs: int, s_len: int, window: int, ring: int):
    """(1, 1, bs) validity of this tile's slots at traced position `pos`."""
    slot = pl.program_id(1) * bs + jax.lax.broadcasted_iota(
        jnp.int32, (1, 1, bs), 2)
    if ring:
        abs_pos = pos - ((pos - slot) % ring)
        valid = abs_pos >= 0
    else:
        abs_pos = slot
        valid = slot <= pos
    valid = valid & (slot < s_len)                 # padded tail slots
    if window:
        valid = valid & (abs_pos > pos - window) & (abs_pos <= pos)
    return valid


def _scores(q, k, pos_ref, *, bs: int, s_len: int, window: int, ring: int):
    s = jax.lax.dot_general(q, k, _QK, preferred_element_type=jnp.float32)
    valid = _tile_mask(pos_ref[pl.program_id(0)], bs, s_len, window, ring)
    return s, valid


def _decode_attn_kernel_packed(pos_ref, q_ref, kd_ref, vd_ref, ks_ref,
                               vs_ref, o_ref, m_ref, l_ref, *, bs: int,
                               s_len: int, window: int, ring: int):
    """One (batch, kv_tile) grid step over an OVP-packed cache.

    pos    (B,)              current absolute positions (scalar prefetch)
    q_ref  (1, Hkv, G, D)    f32 queries, pre-scaled by 1/sqrt(D), in plane
                             layout (even K-lanes in [..., :D/2])
    kd/vd  (1, bs, Hkv, D/2) packed nibble tiles (streamed HBM->VMEM)
    ks/vs  (1, bs, Hkv)      per-(token, head) 3-sigma scales
    o_ref  (1, Hkv, G, D)    f32 accumulator in plane layout
    m/l    (1, Hkv, G, 1)    online-softmax running max / denominator
    """
    init_carry(o_ref, m_ref, l_ref)
    # fold the per-token K scale into the scores, the V scale into the
    # probabilities — the decoded code planes feed the MXU directly
    s, valid = _scores(q_ref[0], decode_kv_tile(kd_ref[0]), pos_ref, bs=bs,
                       s_len=s_len, window=window, ring=ring)
    s = jnp.where(valid, s * jnp.transpose(ks_ref[0])[:, None, :], NEG_INF)
    online_softmax_step(s, decode_kv_tile(vd_ref[0]),
                        jnp.transpose(vs_ref[0])[:, None, :], o_ref, m_ref,
                        l_ref)
    finish(o_ref, l_ref)


def _decode_attn_kernel_fp(pos_ref, q_ref, k_ref, v_ref, o_ref, m_ref,
                           l_ref, *, bs: int, s_len: int, window: int,
                           ring: int):
    """fp16/bf16/f32 cache variant: same body minus the unpack phase, in
    natural lane order."""
    init_carry(o_ref, m_ref, l_ref)
    kt = heads_major(k_ref[0].astype(jnp.float32))        # (Hkv, bs, D)
    s, valid = _scores(q_ref[0], kt, pos_ref, bs=bs, s_len=s_len,
                       window=window, ring=ring)
    online_softmax_step(jnp.where(valid, s, NEG_INF),
                        heads_major(v_ref[0].astype(jnp.float32)), None,
                        o_ref, m_ref, l_ref)
    finish(o_ref, l_ref)


# --------------------------------------------------------------------------
# pallas_call builder + public wrapper
# --------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("packed", "s_len", "window",
                                             "ring", "bs", "interpret"))
def _decode_attn_call(bt, pos, q4, kd, vd, ks, vs, *, packed: bool,
                      s_len: int, window: int, ring: int, bs: int,
                      interpret: bool):
    """q4 (B, Hkv, G, D) f32 queries; kd/vd the cache data and ks/vs its
    (…, Hkv) scales (fp caches pass (1, 1, 1) sentinels the fp body never
    reads); pos (B,) int32. Returns (B, Hkv, G, D) f32.

    `bt` None: kd/vd are the (padded) slab `(B, Sp, Hkv, …)` and the kv
    tile is `bs` rows. Otherwise `bt` is the block table: kd/vd/ks/vs are
    the `(n_pages, page_size, Hkv, …)` pools and the kv/scale index maps
    read the physical page id from it (a scalar-prefetch operand) instead
    of using the grid's kv-tile index directly — one whole page is one kv
    tile, so the gather costs nothing beyond the index indirection, and
    the kernel bodies are shared verbatim with the slab path."""
    b, hkv, g, d = q4.shape
    if bt is None:
        grid = (b, kd.shape[1] // bs)
        prefetch = (pos,)

        def tile(bb, ss, *_):
            return bb, ss
    else:
        grid = (b, bt.shape[1])
        prefetch = (bt, pos)

        def tile(bb, ss, tbl, _):
            return tbl[bb, ss], 0
    kv_spec = pl.BlockSpec((1, bs, hkv, kd.shape[-1]),
                           lambda bb, ss, *r: (*tile(bb, ss, *r), 0, 0))
    scl_spec = pl.BlockSpec((1, bs, hkv),
                            lambda bb, ss, *r: (*tile(bb, ss, *r), 0))
    q_spec = pl.BlockSpec((1, hkv, g, d), lambda bb, ss, *_: (bb, 0, 0, 0))
    carry_spec = pl.BlockSpec((1, hkv, g, 1),
                              lambda bb, ss, *_: (bb, 0, 0, 0))
    out_shapes = (jax.ShapeDtypeStruct((b, hkv, g, d), jnp.float32),
                  jax.ShapeDtypeStruct((b, hkv, g, 1), jnp.float32),
                  jax.ShapeDtypeStruct((b, hkv, g, 1), jnp.float32))
    out_specs = (q_spec, carry_spec, carry_spec)
    if packed:
        body = functools.partial(_decode_attn_kernel_packed, bs=bs,
                                 s_len=s_len, window=window, ring=ring)
        in_specs = [q_spec, kv_spec, kv_spec, scl_spec, scl_spec]
        operands = (q4, kd, vd, ks, vs)
    else:
        body = functools.partial(_decode_attn_kernel_fp, bs=bs,
                                 s_len=s_len, window=window, ring=ring)
        in_specs = [q_spec, kv_spec, kv_spec]
        operands = (q4, kd, vd)

    def kernel(*refs):
        body(*refs[len(prefetch) - 1:])     # the block table is index-only

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch), grid=grid, in_specs=in_specs,
        out_specs=out_specs)
    # `name` is the op's name in the device trace (`_decode_attn_call.<n>`),
    # whatever function wraps the call
    out, _, _ = pl.pallas_call(kernel, grid_spec=grid_spec,
                               out_shape=out_shapes, interpret=interpret,
                               name="_decode_attn_call")(*prefetch, *operands)
    return out


def _pad_s(x, mult, value=0):
    rem = (-x.shape[1]) % mult
    if rem == 0:
        return x
    pads = [(0, 0)] * x.ndim
    pads[1] = (0, rem)
    return jnp.pad(x, pads, constant_values=value)


def _pick_bs(s_len: int, block_s: int) -> int:
    """kv-tile size: the largest divisor of `s_len` <= block_s when a
    reasonable one exists, else block_s (padding kicks in).

    A non-divisor tile forces `_pad_s` to copy the WHOLE cache every
    traced decode step — a per-step full-cache HBM round trip that
    defeats the point of the kernel — so exact tiling wins whenever the
    divisor keeps the grid sane; in-kernel masking covers the padded
    remainder for pathological (e.g. prime) cache lengths. A tile shorter
    than the cache is a multiple of 8 rows, as the TPU's (8, 128) block
    rule asks of the scale tiles' second-to-last dim."""
    bs = min(block_s, s_len)
    if s_len % bs == 0:
        return bs
    for cand in range(bs - bs % 8, 63, -8):
        if s_len % cand == 0:
            return cand
    return bs


def to_planes(x: jax.Array) -> jax.Array:
    """(…, D) -> (…, D) in plane layout: even lanes, then odd lanes."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


def from_planes(x: jax.Array) -> jax.Array:
    """Inverse of `to_planes`: re-interleave the even/odd halves."""
    d2 = x.shape[-1] // 2
    return jnp.stack([x[..., :d2], x[..., d2:]], axis=-1).reshape(x.shape)


def fused_decode_attention(q: jax.Array, cache, pos: jax.Array, *,
                           window: int = 0, ring: int = 0,
                           interpret: bool = False,
                           block_s: int = 256) -> jax.Array:
    """Single-token attention over a KV cache, one pallas_call.

    q: (B, 1, H, D); `cache` an fp ({"k", "v"}) or OVP-packed
    ({"k_data", "v_data", "k_scl", "v_scl"}) cache dict; pos: (B,)
    current absolute position (token at `pos` already written). Length,
    ring and sliding-window masking run in-kernel from the traced `pos`.
    Layout preconditions are `decline_reason`'s job — callers go through
    `backends.decode_attention`, which falls back on a reason code.

    `block_s` tiles the slab's kv dim (a paged cache's tile is its page).
    Packed caches decode to the even/odd plane layout, so the queries go
    in, and the output comes out, through `to_planes` / `from_planes`.
    """
    b, t, h, d = q.shape
    packed = "k_data" in cache
    paged = "block_table" in cache
    kd = cache["k_data"] if packed else cache["k"]
    vd = cache["v_data"] if packed else cache["v"]
    hkv = kd.shape[2]
    g = h // hkv
    qf = q.reshape(b, hkv, g, d).astype(jnp.float32) / math.sqrt(d)
    if packed:
        qf = to_planes(qf)
    pos = pos.reshape(b).astype(jnp.int32)
    bt = None
    if paged:
        # page size IS the kv tile size: no padding, no _pick_bs — each
        # grid step gathers one whole physical page through the table.
        # Logical capacity is pages_per_row * page_size; a ring cache's
        # true length is the ring (the pool rounds it up to whole pages
        # and the mask must exclude the rounding tail).
        bt = cache["block_table"].astype(jnp.int32)
        bs = kd.shape[1]
        s_len = ring if ring else bt.shape[1] * bs
    else:
        s_len = kd.shape[1]
        bs = _pick_bs(s_len, block_s)
        kd, vd = _pad_s(kd, bs), _pad_s(vd, bs)
    if packed:
        ks, vs = cache["k_scl"], cache["v_scl"]
        if not paged:
            ks, vs = _pad_s(ks, bs, value=1.0), _pad_s(vs, bs, value=1.0)
    else:
        # the fp kernel takes no scale refs; tiny sentinels keep the
        # jitted call signature uniform without materializing scale planes
        ks = vs = jnp.zeros((1, 1, 1), jnp.float32)
    out = _decode_attn_call(bt, pos, qf, kd, vd, ks, vs, packed=packed,
                            s_len=s_len, window=window, ring=ring, bs=bs,
                            interpret=interpret)
    if packed:
        out = from_planes(out)
    return out.reshape(b, t, h, d).astype(q.dtype)
