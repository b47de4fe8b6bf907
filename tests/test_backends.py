"""Backend registry + cross-backend equivalence suite.

Every registered execution backend must produce the same quantized matmul
(up to fp32 reassociation) as the pure-jnp reference backend, for every
weight/activation precision the policies can express, per-tensor and
per-channel weight scales, and 2-D / 3-D lhs. The fused Pallas path (one
pallas_call: in-kernel activation quantization + scale epilogue) is
verified against the XLA encode->decode path it replaced.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import backends
from repro.core.ovp import QuantizedTensor, ovp_dequantize, ovp_quantize
from repro.core.policy import QuantPolicy
from repro.core.qlinear import qmatmul, quantize_weight
from repro.core.quantizer import sigma_init_scale
from repro.kernels import ops

from test_ovp import heavy_tailed

# every backend that must agree with "reference" (the fp32 oracle);
# "pallas" (compiled) is the same kernel as "pallas_interpret" and needs a
# TPU, so CPU CI exercises the interpret twin
EQUIV_BACKENDS = ["xla", "pallas_interpret"]

POLICIES = {
    "w4a16": dict(wbits=4, abits=0),
    "w4a4": dict(wbits=4, abits=4),
    "w4a4_flint4": dict(wbits=4, abits=4, w_normal_dtype="flint4",
                        a_normal_dtype="flint4"),
    "w8a8_int8_ovp": dict(wbits=8, abits=8, w_normal_dtype="int8",
                          a_normal_dtype="int8"),
}


def make_policy(kind: str, granularity: str, backend: str) -> QuantPolicy:
    return QuantPolicy(method="olive", compute_dtype="float32",
                       w_granularity=granularity, backend=backend,
                       **POLICIES[kind])


def rel_err(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-9))


@pytest.fixture(scope="module")
def operands():
    key = jax.random.PRNGKey(7)
    ka, kx, kw = jax.random.split(key, 3)
    k, n = 128, 96
    x2 = heavy_tailed(kx, (48, k), outlier_frac=0.01, outlier_scale=9.0)
    x3 = heavy_tailed(ka, (3, 16, k), outlier_frac=0.01, outlier_scale=9.0)
    w = heavy_tailed(kw, (k, n), outlier_frac=0.01, outlier_scale=9.0)
    return x2, x3, w


# lhs of a decode step (slots, 1, K) and of a few rows per slot, in every
# activation mode the fused 2-D kernel takes
DECODE_LHS = [(4, 1, 64), (32, 1, 64), (4, 3, 64)]
DECODE_MODES = ["quantize_tensor", "quantize_row", "codes4", "fp"]


def _decode_matmul(mode, x, w):
    """(fused fn of x, reference result) of one decode-step matmul with
    activations in `mode`."""
    kind = "w4a16" if mode == "fp" else "w4a4"
    pol = make_policy(kind, "channel", "pallas_interpret")
    wq = quantize_weight(w, pol)
    ref = dataclasses.replace(pol, backend="reference")
    if mode == "codes4":
        scale = sigma_init_scale(x, "int4")

        def fn(x):
            return ops.ovp_matmul(ovp_quantize(x, scale, "int4", pair_axis=-1),
                                  wq, interpret=True)

        aq = ovp_quantize(x, scale, "int4", pair_axis=-1)
        return fn, ovp_dequantize(aq) @ ovp_dequantize(wq)
    if mode == "quantize_row":
        pol = dataclasses.replace(pol, act_scale_mode="static")
        ref = dataclasses.replace(ref, act_scale_mode="static")
        rows = jnp.linspace(0.05, 0.4, int(np.prod(x.shape[:-1])))
        scale = rows.reshape(x.shape[:-1] + (1,))
        return (lambda x: qmatmul(x, wq, pol, act_scale=scale),
                qmatmul(x, wq, ref, act_scale=scale))
    return lambda x: qmatmul(x, wq, pol), qmatmul(x, wq, ref)


class TestRegistry:
    def test_expected_backends_registered(self):
        for name in ("xla", "pallas", "pallas_interpret", "reference"):
            assert name in backends.available()

    def test_unknown_backend_raises_with_options(self):
        with pytest.raises(KeyError, match="registered"):
            backends.get_backend("tpu_v9")

    def test_register_and_dispatch_custom_backend(self, operands):
        x2, _, w = operands

        class Doubling(backends.XlaBackend):
            name = "xla_doubled"

            def matmul(self, x, wq, policy, act_scale=None,
                       precision=None, site=""):
                return 2.0 * super().matmul(x, wq, policy,
                                            act_scale, precision, site)

        backends.register(Doubling())
        try:
            pol = make_policy("w4a16", "tensor", "xla_doubled")
            wq = quantize_weight(w, pol)
            got = backends.dispatch(x2, wq, pol)
            want = backends.dispatch(
                x2, wq, dataclasses.replace(pol, backend="xla"))
            np.testing.assert_allclose(np.asarray(got), 2 * np.asarray(want),
                                       rtol=1e-6)
        finally:
            backends._REGISTRY.pop("xla_doubled")


class TestBackendEquivalence:
    @pytest.mark.parametrize("backend", EQUIV_BACKENDS)
    @pytest.mark.parametrize("granularity", ["tensor", "channel"])
    @pytest.mark.parametrize("kind", sorted(POLICIES))
    def test_matches_reference_2d(self, backend, granularity, kind,
                                  operands):
        x2, _, w = operands
        pol = make_policy(kind, granularity, backend)
        wq = quantize_weight(w, pol)
        assert isinstance(wq, QuantizedTensor)
        got = qmatmul(x2, wq, pol)
        want = qmatmul(x2, wq,
                       dataclasses.replace(pol, backend="reference"))
        assert got.shape == want.shape
        assert rel_err(got, want) < 1e-5, (backend, granularity, kind)

    @pytest.mark.parametrize("backend", EQUIV_BACKENDS)
    @pytest.mark.parametrize("kind", sorted(POLICIES))
    def test_matches_reference_3d(self, backend, kind, operands):
        """3-D lhs (serving decode-step layout) takes the same fused path
        with no reshape glue and agrees with the oracle."""
        _, x3, w = operands
        pol = make_policy(kind, "channel", backend)
        wq = quantize_weight(w, pol)
        got = qmatmul(x3, wq, pol)
        want = qmatmul(x3, wq,
                       dataclasses.replace(pol, backend="reference"))
        assert got.shape == x3.shape[:-1] + (w.shape[1],)
        assert rel_err(got, want) < 1e-5, (backend, kind)

    @pytest.mark.parametrize("backend", EQUIV_BACKENDS)
    def test_static_per_row_act_scale(self, backend, operands):
        """Per-row static activation scales flow into the fused prologue /
        epilogue identically across backends."""
        x2, _, w = operands
        pol = dataclasses.replace(
            make_policy("w4a4", "channel", backend),
            act_scale_mode="static")
        wq = quantize_weight(w, pol)
        row_scale = jnp.linspace(0.05, 0.4, x2.shape[0])[:, None]
        got = qmatmul(x2, wq, pol, act_scale=row_scale)
        want = qmatmul(x2, wq,
                       dataclasses.replace(pol, backend="reference"),
                       act_scale=row_scale)
        assert rel_err(got, want) < 1e-5

    @pytest.mark.parametrize("mode", DECODE_MODES)
    @pytest.mark.parametrize("lhs", DECODE_LHS,
                             ids=["x".join(map(str, s)) for s in DECODE_LHS])
    def test_decode_single_row_3d(self, lhs, mode):
        """(…, M, K) decode-step GEMMs fold every leading dim into M: one
        pallas_call whose grid has batch 1 and whose M blocks cover all
        rows, so each weight tile is fetched once per call, not per slot."""
        key = jax.random.PRNGKey(3)
        x = jax.random.normal(key, lhs)
        w = jax.random.normal(jax.random.split(key)[0], (64, 32))
        fn, want = _decode_matmul(mode, x, w)
        got = fn(x)
        assert got.shape == lhs[:-1] + (32,)
        assert rel_err(got, want) < 1e-5
        [eqn] = backends.pallas_call_eqns(fn, x)
        gm = eqn.params["grid_mapping"]
        [out] = gm.block_mappings_output
        bm = out.block_shape[1].block_size
        assert gm.grid[0] == 1
        assert gm.grid[1] * bm >= int(np.prod(lhs[:-1]))


class TestMixedPrecision:
    def test_int8_act_with_4bit_weight_on_pallas(self, operands):
        """Regression: abits=8 with a packed 4-bit weight used to reach
        matmul_w4a4 as an unpacked int8 QuantizedTensor and trip the K/2
        shape assert; it now runs fused and matches the XLA path."""
        x2, x3, w = operands
        pol = QuantPolicy(method="olive", wbits=4, abits=8,
                          compute_dtype="float32",
                          backend="pallas_interpret")
        wq = quantize_weight(w, pol)
        assert wq.is_packed  # 4-bit weight, 8-bit activations
        for x in (x2, x3):
            got = qmatmul(x, wq, pol)
            want = qmatmul(x, wq,
                           dataclasses.replace(pol, backend="xla"))
            assert rel_err(got, want) < 1e-5

    def test_prepacked_int8_activation_tensor(self, operands):
        """ops.ovp_matmul no longer raises NotImplementedError on int8
        OVP operands (one code per byte)."""
        x2, _, w = operands
        wq = ovp_quantize(w, sigma_init_scale(w, "int8"), "int8",
                          pair_axis=0)
        aq = ovp_quantize(x2, sigma_init_scale(x2, "int8"), "int8",
                          pair_axis=-1)
        got = ops.ovp_matmul(aq, wq, interpret=True)
        want = ovp_dequantize(aq) @ ovp_dequantize(wq)
        assert rel_err(got, want) < 1e-5


class TestStackedWeights:
    def test_per_expert_stacked_serves_grouped(self, operands):
        """Stacked (per-expert) weights dispatch cleanly on every backend:
        the grouped Pallas kernel serves them (no XLA fallback — the
        dispatch ledger must show the stack was served, not declined) and
        agrees with the XLA broadcast path. The full grouped matrix lives
        in tests/test_grouped_kernel.py."""
        key = jax.random.PRNGKey(11)
        e, c, k, f = 4, 8, 64, 48
        xg = jax.random.normal(key, (e, c, k))
        ws = jax.random.normal(jax.random.split(key)[0], (e, k, f))
        pol = make_policy("w4a16", "channel", "pallas_interpret")
        wq = quantize_weight(ws, pol)
        assert wq.data.ndim == 3
        backends.reset_dispatch_stats()
        got = backends.dispatch(xg, wq, pol)
        stats = backends.dispatch_stats()
        assert stats.get("pallas_interpret[stacked]") == 1
        assert not any("->fallback:" in tag for tag in stats)
        want = backends.dispatch(
            xg, wq, dataclasses.replace(pol, backend="xla"))
        assert got.shape == (e, c, f)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


class TestFusedSingleDispatch:
    def test_w4a4_is_one_pallas_call(self, operands):
        """Acceptance: fused W4A4 with in-kernel activation quantization
        and in-epilogue scales is a single pallas_call."""
        from repro.backends import count_pallas_calls
        x2, _, w = operands
        pol = make_policy("w4a4", "channel", "pallas_interpret")
        wq = quantize_weight(w, pol)
        scale = sigma_init_scale(x2, "int4")

        def fused(x):
            return ops.fused_ovp_matmul(x, wq, a_dtype="int4",
                                        act_scale=scale, interpret=True)

        assert count_pallas_calls(fused, x2) == 1
        # and the one call matches the XLA encode->decode round trip
        aq = ovp_quantize(x2, scale, "int4", pair_axis=-1)
        want = ovp_dequantize(aq) @ ovp_dequantize(wq)
        assert rel_err(fused(x2), want) < 1e-5
