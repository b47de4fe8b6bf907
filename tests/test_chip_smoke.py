"""chip_smoke.py off the chip: it refuses to run anywhere but on a TPU
with the compiled kernels, never claims success there, and its checks
flag a declined dispatch. Its served path runs here at smoke size on the
interpreter backends, through the functions the chip run calls."""
from __future__ import annotations

import importlib.util
from pathlib import Path

import pytest

from repro import backends
from repro.backends import PallasInterpretBackend

ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_main_fails_without_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    captured = capsys.readouterr()
    assert '"ok": true' not in captured.out
    assert "no TPU" in captured.err


def test_main_fails_without_repo_sources(smoke, capsys, monkeypatch,
                                         tmp_path):
    monkeypatch.setattr(smoke, "ROOT", tmp_path)
    assert smoke.main([]) != 0
    captured = capsys.readouterr()
    assert '"ok": true' not in captured.out
    assert "no repro package" in captured.err


def test_main_fails_on_forced_interpret(smoke, capsys):
    class Forced(PallasInterpretBackend):       # what REPRO_FORCE_INTERPRET
        name = "pallas"                         # registers at import

    real = backends.get_backend("pallas")
    backends.register(Forced())
    try:
        assert smoke.main([]) != 0
    finally:
        backends.register(real)
    captured = capsys.readouterr()
    assert '"ok": true' not in captured.out
    assert "interpreter" in captured.err


def test_decline_detector(smoke):
    served = {"pallas": 7, "pallas[decode_attn]": 2,
              "pallas[prefill_attn]": 2}
    assert smoke.decline_keys(served) == []
    smoke.check_dispatch(served, "pallas")
    declined = dict(served,
                    **{"pallas->fallback:lhs_rank_lt_2": 1})
    assert smoke.decline_keys(declined) == [
        "pallas->fallback:lhs_rank_lt_2"]
    with pytest.raises(smoke.SmokeError, match="declined"):
        smoke.check_dispatch(declined, "pallas")
    # a kernel family that never served is a failure too
    with pytest.raises(smoke.SmokeError, match="decode_attn"):
        smoke.check_dispatch({"pallas": 7, "pallas[prefill_attn]": 2},
                             "pallas")


def test_served_path_at_smoke_size(smoke):
    """The one-chip flow end to end on the interpreter: requests finish,
    nothing declines, and the float32 logits match the reference."""
    res = smoke.run_one_chip(arch="qwen1.5-0.5b-smoke",
                             backend="pallas_interpret", n_requests=2,
                             prompt_len=(20, 40), max_new=2, slots=2,
                             max_len=256)
    assert res["logits_rel_err"] < 1e-5
