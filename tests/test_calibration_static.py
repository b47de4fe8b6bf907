"""Static calibrated activation scales, end to end.

Covers the calibration artifact (save/load round trip, glob resolution),
`apply_calibration` baking scales into a policy program, the engine's
up-front validation of static-mode sites (machine-readable
`MissingStaticScaleError`), static-vs-dynamic numerical agreement when the
static scale equals the dynamic one (all backends, 2-D + grouped), and the
acceptance claim: an engine serving with a calibration artifact performs
ZERO dynamic activation-scale computations (`backends.act_scale_stats()`).
"""
from __future__ import annotations

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import backends
from repro.configs.base import ArchConfig
from repro.core.calibration import (ActTape, CalibrationArtifact,
                                    MissingStaticScaleError,
                                    apply_calibration,
                                    calibrate_activation_scales,
                                    calibrate_model, collecting_activations,
                                    static_scale_misses, uses_static_scales)
from repro.core.policy import OLIVE_W4A4, QuantPolicy
from repro.core.qlinear import qmatmul, quantize_params, quantize_weight
from repro.core.quantizer import sigma_init_scale
from repro.kernels import ops
from repro.models.model import build_model
from repro.serve.engine import EngineCfg, ServingEngine

TINY = ArchConfig(name="cal-tiny", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab=256,
                  head_dim=16, block_pattern=("attn",))


def rel_err(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-9))


def serve_program(backend: str = "xla"):
    """W4A4 static-mode program the engine tests serve under."""
    return QuantPolicy(method="olive", wbits=4, abits=4,
                       act_scale_mode="static", compute_dtype="float32",
                       backend=backend).as_program()


@pytest.fixture(scope="module")
def tiny_fp():
    model = build_model(TINY, QuantPolicy(compute_dtype="float32"),
                        remat=False)
    return model, model.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def artifact(tiny_fp):
    model, params = tiny_fp
    batch = {"tokens": jnp.asarray(
        np.random.default_rng(0).integers(0, TINY.vocab, size=(2, 16))
        .astype(np.int32))}
    return calibrate_model(model, params, [batch], max_per_site=4096,
                           n_grid=8)


class TestArtifact:
    def test_round_trip(self, artifact, tmp_path):
        p = artifact.save(str(tmp_path / "calib.json"))
        loaded = CalibrationArtifact.load(p)
        assert loaded == artifact
        assert loaded.as_dict() == artifact.as_dict()
        # the payload is plain JSON with the declared kind
        with open(p) as f:
            payload = json.load(f)
        assert payload["kind"] == "olive-calibration"
        assert payload["scales"]

    def test_load_rejects_non_artifact(self, tmp_path):
        p = tmp_path / "not_calib.json"
        p.write_text('{"scales": {"a": 1.0}}')
        with pytest.raises(ValueError, match="not a calibration artifact"):
            CalibrationArtifact.load(str(p))

    def test_calibrated_sites_cover_quantized_tree(self, artifact,
                                                   tiny_fp):
        """Tape keys are the same addresses `quantize_params` resolves:
        every quantized leaf of the serving tree has a calibrated scale."""
        model, params = tiny_fp
        prog = apply_calibration(serve_program(), artifact)
        qmodel = build_model(TINY, prog, remat=False)
        qp = quantize_params(qmodel.adapt_params(params), prog)
        assert static_scale_misses(qp, prog) == []
        # per-layer unrolled addresses were taped (layers/<i>/...)
        assert any(s.startswith("layers/0/") for s in artifact.sites())
        assert any(s.startswith("layers/1/") for s in artifact.sites())

    def test_glob_keys_resolve(self):
        art = CalibrationArtifact.from_scales(
            {"layers/0/attn/wq": 0.25, "layers/*/mlp/w*": 0.5})
        assert art.resolve("layers/0/attn/wq") == 0.25
        assert art.resolve("layers/7/mlp/wg") == 0.5
        assert art.resolve("embed/table") is None
        prog = apply_calibration(serve_program(), art)
        assert prog.resolve("layers/3/mlp/wd").static_act_scale == 0.5
        assert prog.resolve("layers/0/attn/wq").static_act_scale == 0.25
        assert prog.resolve("layers/0/attn/wk").static_act_scale is None

    def test_glob_key_preserves_mixed_precision(self):
        """A glob artifact key attaches scales per concrete site without
        disturbing each site's own precision rule: layer 1's W8 rule
        survives a `layers/*/mlp/w*` scale key."""
        w8 = QuantPolicy(method="olive", wbits=8, abits=8,
                         w_normal_dtype="int8", a_normal_dtype="int8",
                         act_scale_mode="static",
                         compute_dtype="float32")
        prog = serve_program().with_rules([("layers/1/mlp/*", w8)])
        art = CalibrationArtifact.from_scales({"layers/*/mlp/w*": 0.5})
        cal = apply_calibration(prog, art)
        hot = cal.resolve("layers/1/mlp/wg")
        cold = cal.resolve("layers/2/mlp/wg")
        assert (hot.wbits, hot.static_act_scale) == (8, 0.5)
        assert (cold.wbits, cold.static_act_scale) == (4, 0.5)
        # the engine's backend override must not drop the overlay
        assert cal.with_backend("reference") \
            .resolve("layers/1/mlp/wg").static_act_scale == 0.5

    def test_overlapping_glob_keys_keep_author_order(self, tmp_path):
        """First key wins for overlapping globs, across a save/load
        round trip (no alphabetical re-sorting)."""
        art = CalibrationArtifact.from_scales(
            {"layers/0/*": 0.5, "layers/*": 0.1})
        loaded = CalibrationArtifact.load(
            art.save(str(tmp_path / "o.json")))
        for a in (art, loaded):
            assert a.resolve("layers/0/attn/wq") == 0.5
            assert a.resolve("layers/3/attn/wq") == 0.1

    def test_reapplied_artifact_fresh_scales_win(self, tmp_path):
        """Re-applying an updated artifact serves the NEW scale for a
        site both cover — in resolution and in the saved payload."""
        prog = apply_calibration(
            serve_program(),
            CalibrationArtifact.from_scales({"layers/0/attn/wq": 0.5}))
        prog2 = apply_calibration(
            prog,
            CalibrationArtifact.from_scales({"layers/0/attn/wq": 0.9}))
        assert prog2.resolve("layers/0/attn/wq").static_act_scale == 0.9
        merged = prog2.artifact
        assert merged.resolve("layers/0/attn/wq") == 0.9
        saved = CalibrationArtifact.load(
            merged.save(str(tmp_path / "m.json")))
        assert saved.resolve("layers/0/attn/wq") == 0.9


class TestStaticDynamicEquivalence:
    @pytest.mark.parametrize("backend",
                             ["xla", "pallas_interpret", "reference"])
    def test_matches_dynamic_when_scale_equal(self, backend):
        """With the static scale set to exactly the dynamic 3σ value, the
        static path reproduces the dynamic output on every backend (the
        Pallas constant-folded prologue to fp32 rounding)."""
        key = jax.random.PRNGKey(5)
        x = jax.random.normal(key, (32, 128)) * 2.0
        w = jax.random.normal(jax.random.split(key)[0], (128, 96))
        dyn = QuantPolicy(method="olive", wbits=4, abits=4,
                          compute_dtype="float32", backend=backend)
        wq = quantize_weight(w, dyn)
        s = float(sigma_init_scale(x, "int4"))
        st = dataclasses.replace(dyn, act_scale_mode="static",
                                 static_act_scale=s)
        got = qmatmul(x, wq, st, site="t")
        want = qmatmul(x, wq, dyn, site="t")
        assert rel_err(got, want) < 1e-5, backend

    def test_grouped_static_matches_dynamic(self):
        """The grouped (per-expert) kernel's static prologue agrees with
        the scale-operand path at the same scale."""
        key = jax.random.PRNGKey(6)
        xg = jax.random.normal(key, (4, 8, 64))
        ws = jax.random.normal(jax.random.split(key)[0], (4, 64, 48))
        pol = QuantPolicy(method="olive", wbits=4, abits=4,
                          compute_dtype="float32")
        wq = quantize_weight(ws, pol)
        s = float(sigma_init_scale(xg, "int4"))
        stat = ops.grouped_ovp_matmul(xg, wq, a_dtype="int4",
                                      static_act_scale=s, interpret=True)
        dyn = ops.grouped_ovp_matmul(xg, wq, a_dtype="int4",
                                     act_scale=jnp.float32(s),
                                     interpret=True)
        assert rel_err(stat, dyn) < 1e-5

    def test_static_path_is_one_pallas_call_scalar_scale_operand(self):
        """The static kernel stays a single dispatch and its activation
        scale is ONE (1, 1) scalar operand, not the (B, M, 1) per-row
        plane the dynamic prologue streams — and because the scale is an
        operand (not a baked constant), one compiled kernel serves every
        calibrated site."""
        key = jax.random.PRNGKey(7)
        x = jax.random.normal(key, (16, 128))
        w = jax.random.normal(jax.random.split(key)[0], (128, 64))
        pol = QuantPolicy(method="olive", wbits=4, abits=4,
                          compute_dtype="float32")
        wq = quantize_weight(w, pol)

        def static_mm(x):
            return ops.fused_ovp_matmul(x, wq, a_dtype="int4",
                                        static_act_scale=0.1,
                                        interpret=True)

        [eqn] = backends.pallas_call_eqns(static_mm, x)
        shapes = [tuple(v.aval.shape) for v in eqn.invars]
        assert (1, 1) in shapes          # the scalar scale word
        assert (x.shape[0], x.shape[1], 1) not in shapes \
            and (1, x.shape[0], 1) not in shapes  # no per-row plane
        # operand, not constant: a second scale at the same shape reuses
        # the compiled kernel instead of tracing a new one
        ops.fused_ovp_matmul(x, wq, a_dtype="int4",
                             static_act_scale=0.1, interpret=True)
        n_traces = ops._fused_padded._cache_size()
        ops.fused_ovp_matmul(x, wq, a_dtype="int4",
                             static_act_scale=0.25, interpret=True)
        assert ops._fused_padded._cache_size() == n_traces


class TestValidation:
    def test_missing_scale_raises_machine_readable(self, tiny_fp):
        """Static mode without an artifact fails engine construction with
        the full miss list, not mid-trace on the first prefill."""
        model, params = tiny_fp
        prog = serve_program()
        qmodel = build_model(TINY, prog, remat=False)
        qp = quantize_params(params, prog)
        with pytest.raises(MissingStaticScaleError) as ei:
            ServingEngine(qmodel, qp, EngineCfg(batch_slots=1, max_len=32))
        assert ei.value.sites  # machine-readable: the offending addresses
        assert all("/" in s for s in ei.value.sites)
        assert "missing_static_scale" in str(ei.value)

    def test_unknown_site_in_artifact_leaves_misses(self, tiny_fp):
        """An artifact that only covers a bogus site leaves every real
        site unscaled — validation reports them all."""
        model, params = tiny_fp
        art = CalibrationArtifact.from_scales({"no/such/site": 0.1})
        prog = apply_calibration(serve_program(), art)
        qp = quantize_params(params, prog)
        misses = static_scale_misses(qp, prog)
        assert misses  # every quantized site is still uncalibrated
        assert "blocks/0/attn/wq" in misses

    def test_scanned_model_with_layer_keys_diagnoses_layout(self, tiny_fp,
                                                            artifact):
        """EngineCfg.calibration on a *scanned* model with layers/<i>
        artifact keys fails with a layout diagnosis, not a bare miss
        list (the keys can never match blocks/<j> sites)."""
        model, params = tiny_fp
        prog = serve_program()
        qmodel = build_model(TINY, prog, remat=False)
        assert not qmodel.unrolled
        qp = quantize_params(params, prog)
        with pytest.raises(ValueError, match="unrolled layers/<i> layout"):
            ServingEngine(qmodel, qp,
                          EngineCfg(batch_slots=1, max_len=32,
                                    calibration=artifact))

    def test_uses_static_scales_gate(self):
        assert uses_static_scales(serve_program())
        assert not uses_static_scales(OLIVE_W4A4)
        assert not uses_static_scales(
            QuantPolicy(compute_dtype="float32"))


class TestEngineStaticServing:
    def test_serves_with_zero_dynamic_scale_resolutions(self, tiny_fp,
                                                        artifact,
                                                        tmp_path):
        """Acceptance: an engine configured with act_scale_mode="static"
        and a calibration artifact serves end to end with zero dynamic
        activation-scale computations, verified via the backend ledger."""
        _, params = tiny_fp
        # round-trip the artifact through disk, as the serve CLI does
        art = CalibrationArtifact.load(
            artifact.save(str(tmp_path / "a.json")))
        prog = apply_calibration(serve_program(), art)
        model = build_model(TINY, prog, remat=False)
        assert model.unrolled  # per-layer scale rules address layers/<i>
        qp = quantize_params(model.adapt_params(params), prog)

        eng = ServingEngine(model, qp, EngineCfg(batch_slots=2, max_len=48))
        backends.reset_act_scale_stats()
        rng = np.random.default_rng(3)
        for _ in range(3):
            eng.submit(rng.integers(0, TINY.vocab, size=6)
                       .astype(np.int32), max_new_tokens=4)
        done = eng.run_until_drained()
        assert len(done) == 3
        assert all(len(r.out_tokens) == 4 for r in done)
        stats = backends.act_scale_stats()
        assert stats.get("dynamic", 0) == 0, stats
        assert stats.get("static", 0) > 0, stats

    def test_engine_cfg_applies_artifact(self, tiny_fp, artifact):
        """EngineCfg.calibration bakes the artifact in at construction;
        without it the same static-mode engine refuses to start."""
        _, params = tiny_fp
        prog = apply_calibration(serve_program(), artifact)
        model = build_model(TINY, prog, remat=False)
        base = serve_program()
        qp = quantize_params(model.adapt_params(params),
                             prog)  # scales don't affect weight packing
        # validation passes only because the cfg supplies the artifact
        eng = ServingEngine(
            build_model(TINY, apply_calibration(base, artifact),
                        remat=False), qp,
            EngineCfg(batch_slots=1, max_len=32, calibration=artifact))
        assert uses_static_scales(eng.model.policy)
        assert static_scale_misses(qp, eng.model.policy) == []


class TestTape:
    def test_collecting_activations_records_sites(self, tiny_fp):
        model, params = tiny_fp
        tape = ActTape(max_per_site=1024)
        batch = {"tokens": jnp.zeros((1, 8), jnp.int32)}
        with collecting_activations(tape):
            model.forward(params, batch, mode="train")
        # scanned stacks trace their body, so block sites need the
        # unrolled twin (calibrate_model handles that); the head site
        # tapes on any layout
        assert "lm_head/w_out" in tape.samples
        scales = calibrate_activation_scales(tape, "int4", n_grid=4)
        assert set(scales) == set(tape.samples)
        assert all(float(s) > 0 for s in scales.values())
