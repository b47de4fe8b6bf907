"""The program's spans against the device's idle time, on a hand-built
trace: the engine's spans on the executor's thread, the front end's turns
and a benchmark span on the event loop's thread."""
import pytest

from perfbench.lib import spans, trace
from perfbench.metrics import (engine_host_ms, idle_engine_share,
                               idle_frontend_share, idle_share,
                               idle_sync_share, step_handoff_ms)

# busy [0,100] [130,300] [420,600] [640,800] [900,1000]
DEVICES = {"/device:TPU:0": [
    ("fusion.1", 0, 100), ("_fused_padded.3", 130, 170),
    ("fusion.2", 420, 180), ("fusion.3", 640, 160),
    ("fusion.4", 900, 150),            # runs past the window's end
]}
EXECUTOR = [
    ("bench.engine_step", 0, 312),
    ("engine.step", 0, 310), ("engine.decode", 200, 50),
    ("engine.token_sync", 250, 55),
    ("bench.engine_step", 338, 274),
    ("engine.step", 340, 270), ("engine.admit", 340, 10),
    ("engine.first_token", 360, 40), ("engine.token_sync", 560, 45),
    ("bench.engine_step", 718, 234),
    ("engine.step", 720, 230), ("engine.token_sync", 880, 60),
]
LOOP = [("bench.window", 0, 1000),
        ("frontend.turn", 315, 15), ("bench.submit", 400, 10),
        ("frontend.turn", 620, 80)]


@pytest.fixture
def tr():
    return trace.from_events(DEVICES, EXECUTOR + LOOP)


@pytest.fixture
def reading(tr):
    return spans.read_trace(tr)


def test_the_three_idle_shares_partition_idle_share(tr, reading):
    shares = {p: spans.idle_share(reading, p)
              for p in ("frontend", "engine", "sync")}
    assert shares == {"frontend": pytest.approx(6.0),
                      "engine": pytest.approx(16.0),
                      "sync": pytest.approx(7.0)}

    class Run:
        pass
    Run.trace = tr
    assert sum(shares.values()) == pytest.approx(idle_share.read(Run))


def test_an_idle_interval_is_split_at_span_boundaries(tr):
    # the gap [300, 420] straddles the end of a step, a turn, both
    # hand-offs and the next step's phases: the midpoint names it whole
    assert ("engine.first_token", pytest.approx(120e-9)) \
        in trace.idle_gaps(tr)
    by_name = {}
    for name, s in spans.idle_by_name(tr):
        by_name[name] = by_name.get(name, 0.0) + s
    assert by_name == {
        "engine.step": pytest.approx(150e-9),
        "engine.token_sync": pytest.approx(30e-9),
        "engine.first_token": pytest.approx(40e-9),
        "engine.admit": pytest.approx(10e-9),
        "handoff.return": pytest.approx(15e-9),
        "handoff.dispatch": pytest.approx(10e-9),
        "frontend.turn": pytest.approx(35e-9)}
    assert sum(by_name.values()) == pytest.approx(290e-9)
    assert spans.idle_by_name(tr)[0] == ("engine.step",
                                         pytest.approx(80e-9))


def test_handoffs_and_engine_host_time(reading):
    # (315 - 310) + (340 - 330) and (620 - 610) + (720 - 700)
    assert reading.handoff_ms == pytest.approx([15e-6, 30e-6])
    # each step less its token sync and first token
    assert reading.engine_host_ms == pytest.approx(
        [(310 - 55) * 1e-6, (270 - 40 - 45) * 1e-6, (230 - 60) * 1e-6])


def test_a_step_after_a_park_has_no_handoff():
    parked = LOOP + [("frontend.turn", 705, 5)]
    r = spans.read_trace(trace.from_events(DEVICES, EXECUTOR + parked))
    assert r.handoff_ms == pytest.approx([15e-6])


def test_metrics_read_the_reading(monkeypatch, reading):
    monkeypatch.setattr(spans, "of", lambda run: reading)
    assert idle_frontend_share.read(None) == pytest.approx(6.0)
    assert idle_engine_share.read(None) == pytest.approx(16.0)
    assert idle_sync_share.read(None) == pytest.approx(7.0)
    assert step_handoff_ms.read(None) == pytest.approx(22.5e-6)
    assert engine_host_ms.read(None) == pytest.approx(185e-6)


def test_nothing_is_read_untraced_or_without_program_spans(tr):
    class Run:
        trace = None
    for m in (idle_frontend_share, idle_engine_share, idle_sync_share,
              step_handoff_ms, engine_host_ms):
        assert m.read(Run) is None
    bare = trace.from_events(DEVICES, [s for s in EXECUTOR + LOOP
                                       if s[0].startswith("bench.")])
    assert spans.read_trace(bare) is None
    assert spans.idle_share(None, "sync") is None
