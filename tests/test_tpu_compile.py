"""Compile the main-path Pallas kernels for a TPU v5e at qwen1.5-0.5b
widths, without a chip.

The TPU compiler is installed next to JAX and compiles for a described
(not attached) `v5e:2x2` topology. It refuses what the interpreter
accepts: block shapes that break the (8, 128) tiling rule, strided lane
slices lowered as gathers, shifts on uint8 vectors. Each case lowers one
kernel entry point on abstract shapes (nothing runs) and compiles it.

The topology is described inside a fixture, never at import or
collection time: only one process at a time may load the TPU library,
and every test worker imports this file. The persistent compilation
cache is off around these compiles (an entry written for a described
chip cannot be read back without one).
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro import backends  # noqa: F401  (registry before kernels)
from repro.core.ovp import QuantizedTensor
from repro.kernels import decode_attn, ops, prefill_attn

# qwen1.5-0.5b: d 1024, d_ff 2816, 16 heads == 16 kv heads, head_dim 64;
# served with 8 slots, max_len 1024, 16-token pages, 128-token chunks
D_MODEL, D_FF, HKV, HEAD_DIM = 1024, 2816, 16, 64
SLOTS, PAGE, PAGES_PER_ROW, CHUNK, STAGE = 8, 16, 64, 128, 512
N_PAGES = SLOTS * PAGES_PER_ROW


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes, kernel: str):
    """Compile `fn` for the described chip; its kernel shows in the HLO,
    and so in the device trace, under the `pallas_call`'s `name`."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert re.search(rf"%{kernel}(\.\d+)? = .*tpu_custom_call", text), kernel
    return compiled


def _w4(wd, ws, k):
    return QuantizedTensor(data=wd, scale=ws, normal_dtype="int4",
                           pair_axis=0, orig_dim=k)


# the benchmark cells' decode step: 32 slots x 1 token, which the wrapper
# folds into one full-dimension bm=32 M block; qwen2-7b d 3584, d_ff 18944
BENCH_SLOTS, D_MODEL_7B, D_FF_7B = 32, 3584, 18944

# (lhs shape, K, N): decode rows (8 slots x 1 token) and one prefill chunk,
# then the cells' 32-slot decode rows at 0.5b and 7b widths
_MATMULS = [((SLOTS, 1, D_MODEL), D_MODEL, D_FF),
            ((1, CHUNK, D_MODEL), D_MODEL, D_FF),
            ((SLOTS, 1, D_FF), D_FF, D_MODEL),
            ((1, CHUNK, D_FF), D_FF, D_MODEL),
            ((BENCH_SLOTS, 1, D_MODEL), D_MODEL, D_FF),
            ((BENCH_SLOTS, 1, D_FF), D_FF, D_MODEL),
            ((BENCH_SLOTS, 1, D_MODEL_7B), D_MODEL_7B, D_FF_7B)]


@pytest.mark.parametrize("a_mode", ["w4a4_dynamic", "w4a16"])
@pytest.mark.parametrize("lhs,k,n", _MATMULS,
                         ids=[f"{'x'.join(map(str, s))}-k{k}-n{n}"
                              for s, k, n in _MATMULS])
def test_fused_matmul_compiles(one_chip, a_mode, lhs, k, n):
    def fn(x, wd, ws):
        w = _w4(wd, ws, k)
        if a_mode == "w4a4_dynamic":
            return ops.fused_ovp_matmul(
                x, w, a_dtype="int4",
                act_scale=jnp.max(jnp.abs(x), axis=-1) / 7.0,
                out_dtype=jnp.bfloat16)
        return ops.fused_ovp_matmul(x, w, out_dtype=jnp.bfloat16)

    _compile(fn, one_chip, (lhs, jnp.bfloat16), ((k // 2, n), jnp.uint8),
             ((1, n), jnp.float32), kernel="_fused_padded")


def test_static_prologue_compiles(one_chip):
    def fn(x, wd, ws, s):
        return ops.fused_ovp_matmul(x, _w4(wd, ws, D_MODEL), a_dtype="int4",
                                    static_act_scale=s,
                                    out_dtype=jnp.bfloat16)

    _compile(fn, one_chip, ((1, CHUNK, D_MODEL), jnp.bfloat16),
             ((D_MODEL // 2, D_MODEL), jnp.uint8),
             ((1, D_MODEL), jnp.float32), ((), jnp.float32),
             kernel="_fused_padded")


def test_grouped_matmul_compiles(one_chip):
    """qwen3-moe-30b-a3b expert widths: d 2048 -> d_ff 768, 128 experts,
    weight-only (the expert-einsum default)."""
    e, c, d, f = 128, 8, 2048, 768

    def fn(x, wd, ws):
        w = QuantizedTensor(data=wd, scale=ws, normal_dtype="int4",
                            pair_axis=1, orig_dim=d)
        return ops.grouped_ovp_matmul(x, w, out_dtype=jnp.bfloat16)

    _compile(fn, one_chip, ((1, e, c, d), jnp.bfloat16),
             ((e, d // 2, f), jnp.uint8), ((e, 1, f), jnp.float32),
             kernel="_grouped_padded")


def test_ovp_encode_compiles(one_chip):
    """The standalone encoder on one prefill chunk of activations."""
    def fn(x, scale):
        return ops.ovp_encode(x, scale)

    _compile(fn, one_chip, ((CHUNK, D_MODEL), jnp.bfloat16),
             ((), jnp.float32), kernel="ovp_encode")


def _pools():
    return [((N_PAGES, PAGE, HKV, HEAD_DIM // 2), jnp.uint8)] * 2 \
        + [((N_PAGES, PAGE, HKV), jnp.float32)] * 2


@pytest.mark.parametrize("precision", ["default", "highest"])
def test_paged_packed_decode_attention_compiles(one_chip, precision):
    def fn(q, kd, vd, ks, vs, bt, pos):
        cache = dict(k_data=kd, v_data=vd, k_scl=ks, v_scl=vs,
                     block_table=bt)
        return decode_attn.fused_decode_attention(q, cache, pos)

    with jax.default_matmul_precision(precision):
        _compile(fn, one_chip, ((SLOTS, 1, HKV, HEAD_DIM), jnp.bfloat16),
                 *_pools(), ((SLOTS, PAGES_PER_ROW), jnp.int32),
                 ((SLOTS,), jnp.int32), kernel="_decode_attn_call")


@pytest.mark.parametrize("precision", ["default", "highest"])
def test_packed_fused_prefill_compiles(one_chip, precision):
    def fn(q, kd, vd, ks, vs, bt, sk, sv, positions):
        cache = dict(k_data=kd, v_data=vd, k_scl=ks, v_scl=vs,
                     block_table=bt, stage_k=sk, stage_v=sv)
        out, new = prefill_attn.fused_prefill_attention(q, cache, positions)
        return out, new["k_data"], new["k_scl"]

    stage = ((1, STAGE, HKV, HEAD_DIM), jnp.float32)
    with jax.default_matmul_precision(precision):
        _compile(fn, one_chip, ((1, CHUNK, HKV, HEAD_DIM), jnp.bfloat16),
                 *_pools(), ((1, PAGES_PER_ROW), jnp.int32), stage, stage,
                 ((1, CHUNK), jnp.int32), kernel="_prefill_call")
