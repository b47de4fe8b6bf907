"""A configuration brings its own architecture: a second block
(`toy_arch`, heads wider than hidden / heads, no QKV bias, leaves of its
own names) is sized, counted, served and checked through `perfbench/lib`
as Qwen2 is, found by the module name its configuration gives."""
import dataclasses
import json
import time

import jax
import pytest

from perfbench.lib import bench, control, cost, model_config
from perfbench.tests import tiny, toy_arch

TOY = model_config.ModelConfig(
    name="toy", hidden_size=64, intermediate_size=128, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=2, vocab_size=500,
    rope_theta=1e6, rms_norm_eps=1e-6, tie_word_embeddings=False,
    arch="yi-6b", quant="olive_serve", backend="xla",
    reference="perfbench.tests.toy_arch", head_dim=32)

# Between what sound runs of the toy read (median rank 4-8 on eight
# seeds, 513-549 tokens each) and the least that the 3-bit control reads
# on them (43-65), as `tiny.LIMIT` is set.
LIMIT = 20.0


@pytest.fixture(autouse=True)
def _restore_jax_config(tmp_path, monkeypatch):
    """A run turns on the persistent compilation cache for the process;
    here it goes to a temporary directory, and later tests in this worker
    must not inherit it."""
    monkeypatch.setattr(bench, "CACHE_DIR", tmp_path / "jax_cache")
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_compilation_cache_max_size")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    from jax.experimental.compilation_cache import compilation_cache
    compilation_cache.reset_cache()


def test_a_file_names_its_module_and_its_head_size(tmp_path):
    raw = {"hidden_size": 64, "intermediate_size": 128,
           "num_hidden_layers": 2, "num_attention_heads": 4,
           "num_key_value_heads": 2, "head_dim": 32, "vocab_size": 500,
           "rope_theta": 1e6, "rms_norm_eps": 1e-6,
           "tie_word_embeddings": False, "reduced": [],
           "published": {"n_routed_experts": 8},
           "program": {"arch": "yi-6b", "quant": "olive_serve",
                       "backend": "xla"},
           "reference": "perfbench.tests.toy_arch"}
    path = tmp_path / "toy.json"
    path.write_text(json.dumps(raw))
    cfg = model_config.load(path, "toy")
    assert cfg == TOY and cfg.head_dim == 32 and cfg.mesh == ()
    assert model_config.architecture(cfg) is toy_arch
    assert cfg.raw["published"] == {"n_routed_experts": 8}
    with pytest.raises(TypeError):
        cfg.raw["head_dim"] = 16
    # without the key, the head size is hidden / heads, as Qwen2 has it
    del raw["head_dim"]
    path.write_text(json.dumps(raw))
    assert model_config.load(path, "toy").head_dim == 16


def test_the_program_is_sized_by_the_module():
    bench.import_program()
    arch = bench.arch_config(TOY)
    assert (arch.d_model, arch.n_heads, arch.head_dim, arch.qkv_bias,
            arch.rope_theta) == (64, 4, 32, False, 1e6)


def test_matmuls_and_operations_carry_the_modules_shapes():
    assert cost.layer_matmuls(TOY) == [
        ("q_proj", 64, 128), ("k_proj", 64, 64), ("v_proj", 64, 64),
        ("o_proj", 128, 64), ("gate_proj", 64, 128), ("up_proj", 64, 128),
        ("down_proj", 128, 64)]
    assert cost.step_ovp_matmuls(TOY, 4) == [
        (4, k, n) for _, k, n in cost.layer_matmuls(TOY)] * 2
    weights = 2 * (3 * 64 * 128 + 2 * 64 * 64 + 2 * 128 * 64) + 64 * 500
    assert cost.flops_per_token(TOY, 10) \
        == 2 * weights + 4 * 10 * 4 * 32 * 2


def test_the_toy_is_served_and_checked_through_the_harness():
    cell = dataclasses.replace(tiny.cell(LIMIT), name="toy.closed",
                               config=TOY)
    out = bench.run(cell, 2 ** 35 + 17, 2.0, False, time.monotonic(),
                    require_tpu=False,
                    controls={"ovp3": control.CONTROLS["ovp3"]()})
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["checks"]["declines"]["value"] == 0
    ctl = out["controls"]["ovp3"]
    assert ctl["correct"] is False
    assert ctl["checks"]["median_rank"]["value"] > LIMIT
