"""Fused decode-attention kernel equivalence + dispatch suite.

The fused kernel (kernels/decode_attn.py) must match the dense XLA path
across fp and OVP-packed caches, GQA group sizes, ring + sliding-window
masks, and mixed active lengths in one batch; unsupported layouts must
decline with machine-readable reasons and fall back through the registry;
and a quantized-cache ServingEngine decode must never trace a full-cache
dequant (the bug this kernel fixes).

Note on tolerances: for packed caches the LEGACY dense path dequantizes
to bf16 before the einsum; the fused kernel keeps the decoded values in
f32. The kernel is compared tightly (1e-5) against an f32 dequant
reference and loosely (2e-2) against the legacy bf16 materialization.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import backends
from repro.configs.base import ArchConfig
from repro.core.policy import QuantPolicy
from repro.kernels import decode_attn as DA
from repro.models import layers as L
from repro.models.model import build_model
from repro.serve.engine import EngineCfg, ServingEngine

KB = "pallas_interpret"   # kernel backend under test (CPU interpreter)


def _mk_cache(rng, b, s, hkv, d, kv_bits, dtype=jnp.float32, ring=0,
              n_tok=None):
    cache = L.make_kv_cache(b, s, hkv, d, dtype, kv_bits)
    n_tok = s if n_tok is None else n_tok
    k = jnp.asarray(rng.standard_normal((b, n_tok, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, n_tok, hkv, d)), jnp.float32)
    return L.cache_write(cache, k, v, jnp.zeros((b,), jnp.int32),
                         ring=ring)


def _f32_reference(q, cache, pos, **kw):
    """Dense path on an f32 dequant of the cache (packed caches: tight
    oracle without the legacy bf16 rounding)."""
    k, v = DA.read_cache_dense(cache, dtype=jnp.float32)
    return DA.xla_decode_attention(q, {"k": k, "v": v}, pos, **kw)


def _fused(q, cache, pos, **kw):
    return DA.fused_decode_attention(q, cache, pos, interpret=True,
                                     block_s=8, **kw)


@pytest.mark.parametrize("g", [1, 2, 4])
@pytest.mark.parametrize("kv_bits", [0, 4])
def test_fused_matches_dense_gqa(g, kv_bits):
    rng = np.random.default_rng(0)
    b, s, hkv, d = 2, 20, 2, 16
    cache = _mk_cache(rng, b, s, hkv, d, kv_bits)
    q = jnp.asarray(rng.standard_normal((b, 1, hkv * g, d)), jnp.float32)
    pos = jnp.asarray([5, 19], jnp.int32)
    got = _fused(q, cache, pos)
    assert float(jnp.max(jnp.abs(got - _f32_reference(q, cache, pos)))) \
        < 1e-5
    # legacy dense path (bf16 dequant for packed caches): loose agreement
    legacy = DA.xla_decode_attention(q, cache, pos)
    assert float(jnp.max(jnp.abs(got - legacy))) < (2e-2 if kv_bits
                                                    else 1e-5)


def test_fused_matches_dense_bf16_cache():
    rng = np.random.default_rng(1)
    b, s, hkv, d = 2, 16, 2, 8
    cache = _mk_cache(rng, b, s, hkv, d, 0, dtype=jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((b, 1, 4, d)), jnp.float32)
    pos = jnp.asarray([3, 15], jnp.int32)
    got = _fused(q, cache, pos)
    # tight vs the f32 view of the same bf16 values; loose vs the legacy
    # path, which also rounds the probabilities to bf16
    assert float(jnp.max(jnp.abs(got - _f32_reference(q, cache, pos)))) \
        < 1e-5
    assert float(jnp.max(jnp.abs(
        got - DA.xla_decode_attention(q, cache, pos)))) < 2e-2


@pytest.mark.parametrize("kv_bits", [0, 4])
def test_ring_buffer_and_window(kv_bits):
    """Sliding-window ring cache: slot absolute positions reconstructed
    arithmetically in-kernel, wrap-around masked identically to dense."""
    rng = np.random.default_rng(2)
    b, ring, hkv, d, window = 2, 8, 2, 8, 8
    cache = _mk_cache(rng, b, ring, hkv, d, kv_bits, ring=ring)
    q = jnp.asarray(rng.standard_normal((b, 1, 4, d)), jnp.float32)
    for pos in ([13, 21], [7, 8]):
        pos = jnp.asarray(pos, jnp.int32)
        got = _fused(q, cache, pos, window=window, ring=ring)
        want = _f32_reference(q, cache, pos, window=window, ring=ring)
        assert float(jnp.max(jnp.abs(got - want))) < 1e-5


@pytest.mark.parametrize("kv_bits", [0, 4])
def test_sliding_window_no_ring(kv_bits):
    rng = np.random.default_rng(3)
    b, s, hkv, d = 2, 24, 2, 8
    cache = _mk_cache(rng, b, s, hkv, d, kv_bits)
    q = jnp.asarray(rng.standard_normal((b, 1, 2, d)), jnp.float32)
    pos = jnp.asarray([9, 23], jnp.int32)
    got = _fused(q, cache, pos, window=4)
    want = _f32_reference(q, cache, pos, window=4)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5


def test_mixed_active_lengths_one_batch():
    """One compiled kernel serves every active-length mix: positions are a
    traced operand, masking runs in-kernel."""
    rng = np.random.default_rng(4)
    b, s, hkv, d = 4, 32, 2, 16
    cache = _mk_cache(rng, b, s, hkv, d, 4)
    q = jnp.asarray(rng.standard_normal((b, 1, 4, d)), jnp.float32)
    fused = jax.jit(lambda q, c, p: _fused(q, c, p))
    for pos in ([0, 7, 18, 31], [31, 1, 1, 30]):
        pos = jnp.asarray(pos, jnp.int32)
        got = fused(q, cache, pos)
        want = _f32_reference(q, cache, pos)
        assert float(jnp.max(jnp.abs(got - want))) < 1e-5


def test_non_divisible_cache_length_avoids_per_step_pad():
    """A cache length that is no multiple of block_s must tile on an
    exact divisor when a sane one exists (a non-divisor tile would copy
    the whole cache through jnp.pad every traced decode step) — and stay
    correct either way. A tile shorter than the cache is a multiple of 8
    rows (the TPU block rule for the scale tiles)."""
    assert DA._pick_bs(384, 256) == 192      # exact divisor, no padding
    assert DA._pick_bs(1024, 256) == 256
    assert DA._pick_bs(1021, 256) == 256     # prime: pad + in-kernel mask
    assert DA._pick_bs(300, 256) == 256      # no 8-aligned divisor: pad
    rng = np.random.default_rng(9)
    for s in (384, 300, 97):                 # divisor-tiled and padded
        cache = _mk_cache(rng, 2, s, 2, 8, 4)
        q = jnp.asarray(rng.standard_normal((2, 1, 4, 8)), jnp.float32)
        pos = jnp.asarray([s // 3, s - 1], jnp.int32)
        got = DA.fused_decode_attention(q, cache, pos, interpret=True,
                                        block_s=256)
        want = _f32_reference(q, cache, pos)
        assert float(jnp.max(jnp.abs(got - want))) < 1e-5


def test_single_pallas_call_per_site():
    rng = np.random.default_rng(5)
    cache = _mk_cache(rng, 2, 16, 2, 8, 4)
    q = jnp.asarray(rng.standard_normal((2, 1, 4, 8)), jnp.float32)
    pos = jnp.asarray([3, 15], jnp.int32)
    n = backends.count_pallas_calls(
        lambda q, p: _fused(q, cache, p), q, pos)
    assert n == 1


# ---------------------------------------------------------------- declines
def test_decline_reasons():
    rng = np.random.default_rng(6)
    cache = _mk_cache(rng, 2, 8, 2, 8, 4)
    q1 = jnp.zeros((2, 1, 4, 8))
    assert DA.decline_reason(q1, cache) is None
    assert DA.decline_reason(jnp.zeros((2, 2, 4, 8)), cache) \
        == "decode_q_tokens_gt_1"
    odd = _mk_cache(rng, 2, 8, 2, 7, 0)
    assert DA.decline_reason(jnp.zeros((2, 1, 4, 7)), odd) \
        == "decode_head_dim_odd"
    empty = L.make_kv_cache(2, 0, 2, 8, jnp.float32, 0)
    assert DA.decline_reason(jnp.zeros((2, 1, 4, 8)), empty) \
        == "decode_empty_cache"
    assert DA.decline_reason(q1, {"rec": jnp.zeros((2, 8))}) \
        == "decode_no_kv_cache"
    # backend objects expose the same vocabulary; dense backends serve all
    kb = backends.get_backend(KB)
    assert kb.fuses_decode_attention
    assert kb.decode_attn_decline_reason(jnp.zeros((2, 2, 4, 8)), cache) \
        == "decode_q_tokens_gt_1"
    assert backends.get_backend("xla").decode_attn_decline_reason(
        jnp.zeros((2, 2, 4, 8)), cache) is None


def test_dispatch_served_and_fallback_stats():
    rng = np.random.default_rng(7)
    pol = QuantPolicy(method="olive", kv_bits=4, compute_dtype="float32",
                      backend=KB)
    cache = _mk_cache(rng, 2, 16, 2, 8, 4)
    q = jnp.asarray(rng.standard_normal((2, 1, 4, 8)), jnp.float32)
    pos = jnp.asarray([3, 15], jnp.int32)
    backends.reset_dispatch_stats()
    got = L.decode_attention(q, cache, pos, policy=pol)
    assert backends.dispatch_stats() == {f"{KB}[decode_attn]": 1}
    assert float(jnp.max(jnp.abs(
        got - _f32_reference(q, cache, pos)))) < 1e-5

    # declined layout: odd head_dim fp cache -> dense fallback, reason
    # recorded, output identical to the dense path
    odd = _mk_cache(rng, 2, 8, 2, 7, 0)
    q7 = jnp.asarray(rng.standard_normal((2, 1, 4, 7)), jnp.float32)
    p7 = jnp.asarray([3, 7], jnp.int32)
    backends.reset_dispatch_stats()
    got = L.decode_attention(q7, odd, p7, policy=pol)
    assert backends.dispatch_stats() == {
        f"{KB}->fallback:decode_head_dim_odd[decode_attn]": 1}
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(DA.xla_decode_attention(q7, odd, p7)))


def test_make_kv_cache_odd_head_dim_raises():
    with pytest.raises(ValueError, match="even head_dim"):
        L.make_kv_cache(2, 16, 2, 7, kv_bits=4)
    # fp caches stay constructible at any head_dim
    assert "k" in L.make_kv_cache(2, 16, 2, 7, kv_bits=0)


# ------------------------------------------------- cross-attention padding
def test_padded_encoder_cross_attention_matches_tight_cache():
    """enc_len < cache length: the zero-initialized tail rows must score
    -inf, not logit 0 — padded and tight caches agree bit-for-bit."""
    rng = np.random.default_rng(8)
    cfg = ArchConfig(name="xattn-tiny", family="dense", n_layers=1,
                     d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
                     vocab=64, head_dim=8, block_pattern=("attn",))
    pol = QuantPolicy(compute_dtype="float32")
    p = L.attention_params(jax.random.PRNGKey(0), cfg.d_model, cfg.n_heads,
                           cfg.n_kv_heads, cfg.head_dim)
    b, enc_len = 2, 10
    enc_out = jnp.asarray(rng.standard_normal((b, enc_len, cfg.d_model)),
                          jnp.float32)
    x_pre = jnp.asarray(rng.standard_normal((b, 3, cfg.d_model)),
                        jnp.float32)
    x_tok = jnp.asarray(rng.standard_normal((b, 1, cfg.d_model)),
                        jnp.float32)

    def run(cache_len):
        cache = L.make_kv_cache(b, cache_len, cfg.n_kv_heads, cfg.head_dim,
                                jnp.float32, 0, track_len=True)
        positions = jnp.broadcast_to(jnp.arange(3)[None], (b, 3))
        _, cache = L.attention_forward(p, x_pre, positions, cfg, pol,
                                       causal=False, cache=cache,
                                       mode="prefill", kv_x=enc_out,
                                       use_rope=False)
        assert int(cache["src_len"][0]) == min(enc_len, cache_len)
        out, _ = L.attention_forward(p, x_tok, jnp.full((b, 1), 3), cfg,
                                     pol, cache=cache, mode="decode",
                                     kv_x=jnp.zeros_like(x_tok),
                                     use_rope=False)
        return np.asarray(out)

    np.testing.assert_array_equal(run(enc_len), run(enc_len + 6))


# --------------------------------------------------- engine: zero dequants
TINY = ArchConfig(name="kv-decode-tiny", family="dense", n_layers=2,
                  d_model=64, n_heads=4, n_kv_heads=2, d_ff=128, vocab=256,
                  head_dim=16, block_pattern=("attn",))


def test_engine_quantized_decode_zero_full_dequant(monkeypatch):
    """With kv_bits=4 on a kernel backend, a full engine run must never
    trace a full-cache dequant: the fused kernel serves every attention
    site (dispatch stats), and `dequant_kv` is poisoned for the decode
    phase to prove no dense rematerialization hides in the traced step."""
    pol = QuantPolicy(method="olive", wbits=4, abits=0, kv_bits=4,
                      compute_dtype="float32", backend=KB)
    model = build_model(TINY, pol, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, EngineCfg(batch_slots=2, max_len=64))
    rng = np.random.default_rng(0)
    for n in (5, 9, 3):
        eng.submit(rng.integers(0, TINY.vocab, size=n).astype(np.int32),
                   max_new_tokens=4)
    backends.reset_dispatch_stats()

    def _poisoned(data, scl):
        raise AssertionError("full-cache dequant traced in decode")

    # every dense dequant (cache_read included) funnels through this one
    monkeypatch.setattr(DA, "dequant_kv", _poisoned)
    done = eng.run_until_drained()
    assert sorted(len(r.out_tokens) for r in done) == [4, 4, 4]
    stats = backends.dispatch_stats()
    decode_keys = {k: v for k, v in stats.items() if "[decode_attn]" in k}
    assert decode_keys.get(f"{KB}[decode_attn]", 0) >= 1
    assert not any("->fallback:" in k for k in decode_keys)


# ----------------------------------------------------- paged == slab
def _paged_from_slab(slab, ps, bt_rows, n_pages):
    """Scatter a slab cache's rows into a page pool through a block
    table: paged view of the exact same bytes. Unowned pages are filled
    with garbage to prove the table (not page order) selects the data."""
    rng = np.random.default_rng(99)
    bt = np.asarray(bt_rows, np.int32)
    paged = {"block_table": jnp.asarray(bt)}
    for key, leaf in slab.items():
        if key not in ("k", "v", "k_data", "v_data", "k_scl", "v_scl"):
            continue
        arr = np.asarray(leaf)
        b, s = arr.shape[:2]
        tiles = arr.reshape((b, s // ps, ps) + arr.shape[2:])
        if arr.dtype == np.uint8:
            pool = rng.integers(0, 255, (n_pages, ps) + arr.shape[2:],
                                dtype=np.uint8)
        else:
            pool = rng.standard_normal(
                (n_pages, ps) + arr.shape[2:]).astype(arr.dtype)
        for i in range(b):
            for j in range(s // ps):
                pool[bt[i, j]] = tiles[i, j]
        paged[key] = jnp.asarray(pool)
    return paged


@pytest.mark.parametrize("kv_bits,dtype", [(4, jnp.float32),
                                           (0, jnp.bfloat16)])
@pytest.mark.parametrize("g", [1, 2, 4])
def test_paged_matches_slab_bit_for_bit(kv_bits, dtype, g):
    """The paged kernel is the slab kernel plus one block-table
    indirection on the kv-tile grid dim — with slab block_s = page_size
    the tile arithmetic is identical, so outputs match bit-for-bit even
    on permuted, fragmented page layouts and non-divisible lengths."""
    rng = np.random.default_rng(10)
    b, s, ps, hkv, d = 2, 24, 8, 2, 16
    slab = _mk_cache(rng, b, s, hkv, d, kv_bits, dtype=dtype, n_tok=19)
    paged = _paged_from_slab(slab, ps, [[5, 2, 9], [0, 7, 3]], 12)
    q = jnp.asarray(rng.standard_normal((b, 1, hkv * g, d)), jnp.float32)
    for pos in ([5, 18], [18, 0]):          # non-divisible active lengths
        pos = jnp.asarray(pos, jnp.int32)
        got = DA.fused_decode_attention(q, paged, pos, interpret=True)
        want = DA.fused_decode_attention(q, slab, pos, interpret=True,
                                         block_s=ps)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        # dense fallback materializes through the same table: also exact
        np.testing.assert_array_equal(
            np.asarray(DA.xla_decode_attention(q, paged, pos)),
            np.asarray(DA.xla_decode_attention(q, slab, pos)))


@pytest.mark.parametrize("kv_bits", [0, 4])
def test_paged_ring_window_matches_slab(kv_bits):
    rng = np.random.default_rng(11)
    b, ring, ps, hkv, d, window = 2, 16, 8, 2, 8, 8
    slab = _mk_cache(rng, b, ring, hkv, d, kv_bits, ring=ring)
    paged = _paged_from_slab(slab, ps, [[3, 1], [6, 0]], 8)
    q = jnp.asarray(rng.standard_normal((b, 1, 4, d)), jnp.float32)
    for pos in ([13, 21], [7, 40]):
        pos = jnp.asarray(pos, jnp.int32)
        got = DA.fused_decode_attention(q, paged, pos, interpret=True,
                                        window=window, ring=ring)
        want = DA.fused_decode_attention(q, slab, pos, interpret=True,
                                         block_s=ps, window=window,
                                         ring=ring)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_paged_fragmented_pool_matches_slab():
    """Alloc/free interleaving leaves a request's pages scattered across
    the pool; attention through the resulting block table must still be
    bit-identical to the contiguous slab."""
    from repro.serve.paging import PagePool
    rng = np.random.default_rng(12)
    b, s, ps, hkv, d = 2, 32, 8, 2, 16
    pool = PagePool(16, ps)
    pool.alloc(3, owner=100)                   # churn: stagger the frees
    row0 = pool.alloc(4, owner=1)
    pool.free(100)
    row1 = pool.alloc(4, owner=2)              # lands in the freed holes
    assert row1 != sorted(row1) or row1[0] < row0[-1]  # truly fragmented
    slab = _mk_cache(rng, b, s, hkv, d, 4)
    paged = _paged_from_slab(slab, ps, [row0, row1], 16)
    q = jnp.asarray(rng.standard_normal((b, 1, 4, d)), jnp.float32)
    pos = jnp.asarray([31, 11], jnp.int32)
    got = DA.fused_decode_attention(q, paged, pos, interpret=True)
    want = DA.fused_decode_attention(q, slab, pos, interpret=True,
                                     block_s=ps)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_paged_single_pallas_call():
    rng = np.random.default_rng(13)
    slab = _mk_cache(rng, 2, 16, 2, 8, 4)
    paged = _paged_from_slab(slab, 8, [[1, 4], [2, 5]], 8)
    q = jnp.asarray(rng.standard_normal((2, 1, 4, 8)), jnp.float32)
    pos = jnp.asarray([3, 15], jnp.int32)
    n = backends.count_pallas_calls(
        lambda q, p: DA.fused_decode_attention(q, paged, p,
                                               interpret=True), q, pos)
    assert n == 1


def test_paged_decline_reasons():
    rng = np.random.default_rng(14)
    slab = _mk_cache(rng, 2, 16, 2, 8, 4)
    paged = _paged_from_slab(slab, 8, [[1, 4], [2, 5]], 8)
    q = jnp.zeros((2, 1, 4, 8))
    assert DA.decline_reason(q, paged) is None
    assert DA.decline_reason(q, {"block_table": paged["block_table"]}) \
        == "paged_no_pool"
    bad_rank = dict(paged, block_table=paged["block_table"][..., None])
    assert DA.decline_reason(q, bad_rank) == "paged_table_rank"
    bad_dtype = dict(paged,
                     block_table=paged["block_table"].astype(jnp.float32))
    assert DA.decline_reason(q, bad_dtype) == "paged_table_rank"
    odd = {key: (leaf[:, :7] if key != "block_table" else leaf)
           for key, leaf in paged.items()}
    assert DA.decline_reason(q, odd) == "paged_page_misaligned"
    empty = dict(paged, block_table=paged["block_table"][:, :0])
    assert DA.decline_reason(q, empty) == "decode_empty_cache"


def test_engine_backend_override_reaches_decode_attention():
    """EngineCfg.backend rewrites the policy backend for decode-attention
    sites too: an xla-policy model overridden to the kernel backend must
    serve decode attention fused."""
    pol = QuantPolicy(method="olive", wbits=4, abits=0, kv_bits=4,
                      compute_dtype="float32", backend="xla")
    model = build_model(TINY, pol, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params,
                        EngineCfg(batch_slots=1, max_len=64, backend=KB))
    backends.reset_dispatch_stats()
    eng.submit(np.arange(5, dtype=np.int32), max_new_tokens=3)
    eng.run_until_drained()
    stats = backends.dispatch_stats()
    assert stats.get(f"{KB}[decode_attn]", 0) >= 1
    assert not any("->fallback:" in k and "[decode_attn]" in k
                   for k in stats)
