"""A whole run on the CPU at toy size, without the look for a chip: the
output check passes the served path as it is, and fails it when the
timed path is broken underneath or replaced by a lower-precision one."""
import time

import jax
import pytest

from perfbench.lib import bench, control, faults
from perfbench.tests import tiny


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


def _run(seed, **kw):
    return bench.run(tiny.cell(), seed, 2.0, False, time.monotonic(),
                     require_tpu=False, **kw)


def test_the_served_path_as_it_is_is_correct():
    out = _run(2 ** 33 + 5)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert out["checks"]["declines"]["value"] == 0
    assert out["checks"]["window_compiles"]["value"] == 0
    assert set(out["metrics"]) == {"out_tok_s", "itl_p95_ms", "setup_s"}


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_a_broken_timed_path_is_not_correct(fault):
    out = _run(7, fault=faults.FAULTS[fault])
    assert not out["correct"]
    assert out["checks"]["median_rank"]["value"] \
        > out["checks"]["median_rank"]["limit"]


def test_the_lower_precision_control_fails_the_limit():
    out = _run(11, controls={"ovp2": control.CONTROLS["ovp2"]()})
    ctl = out["controls"]["ovp2"]
    assert ctl["correct"] is False
    assert ctl["checks"]["median_rank"]["value"] > tiny.LIMIT
