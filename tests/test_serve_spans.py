"""The serving stack's profiler spans and step counters.

A tiny paged engine serves under `AsyncFrontend` inside
`jax.profiler.trace`; the `.xplane.pb` read back with `ProfileData` holds
one `engine.step` span per step, carrying `step_num == StepEvents.step`,
every `engine.<phase>` span inside its step, a `frontend.turn` between
each two steps, and as many `engine.tables` spans in a step as
`StepEvents.table_uploads` counts. The `MetricsLedger` carries the same
counters, the phases' distributions and the inter-token gaps.
"""
from __future__ import annotations

import asyncio
import glob
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs.base import ArchConfig
from repro.core.policy import QuantPolicy
from repro.models.model import build_model
from repro.serve import AsyncFrontend, EngineCfg, MetricsLedger, ServingEngine
from repro.serve.paging import PagePoolCfg

TINY = ArchConfig(name="spans-tiny", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab=256,
                  head_dim=16, block_pattern=("attn",))
PHASES = {"admit", "prefill_chunk", "first_token", "decode", "token_sync",
          "emit", "tables"}


class _Sink(MetricsLedger):
    """The ledger, also keeping each step's `StepEvents`."""

    def __init__(self):
        super().__init__()
        self.events = []

    def on_step(self, ev, engine):
        self.events.append(ev)
        return super().on_step(ev, engine)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """(engine, sink, spans [(name, start_ns, end_ns, stats)]) of one
    traced serve: 5 prompts on 2 slots, 16-token chunks."""
    model = build_model(TINY, QuantPolicy(compute_dtype="float32"),
                        remat=False)
    eng = ServingEngine(model, model.init(jax.random.PRNGKey(1)), EngineCfg(
        batch_slots=2, max_len=128, page_pool=PagePoolCfg(page_size=16),
        prefill_chunk=16))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, TINY.vocab, n).astype(np.int32)
               for n in (5, 40, 20, 9, 33)]
    sink = _Sink()

    async def go():
        async with AsyncFrontend(eng, metrics=sink) as fe:
            streams = [fe.submit(p, max_new_tokens=4) for p in prompts]
            for s in streams:
                async for _ in s:
                    pass

    out = tmp_path_factory.mktemp("profile")
    with jax.profiler.trace(str(out)):
        asyncio.run(go())
    files = glob.glob(str(Path(out) / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    assert len(files) == 1, files
    from jax.profiler import ProfileData
    spans = []
    for plane in ProfileData.from_file(files[0]).planes:
        for line in plane.lines:
            spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                       {k: v for k, v in e.stats}) for e in line.events
                      if e.name.startswith(("engine.", "frontend."))]
    return eng, sink, sorted(spans, key=lambda s: s[1])


def _steps(spans):
    return [s for s in spans if s[0] == "engine.step"]


def test_each_step_span_carries_its_step_number(served):
    eng, sink, spans = served
    assert len(sink.events) == eng.steps_run > 5
    assert [s[3]["step_num"] for s in _steps(spans)] \
        == [ev.step for ev in sink.events]


def test_every_phase_nests_in_its_step(served):
    _, sink, spans = served
    steps = _steps(spans)
    phases = [s for s in spans if s[0].startswith("engine.")
              and s[0] != "engine.step"]
    assert {s[0].split(".", 1)[1] for s in phases} == PHASES
    for name, a, b, _ in phases:
        assert sum(s <= a and b <= e for _, s, e, _ in steps) == 1, name
    # a step's phase seconds are timed around its phases' spans
    for ev, (_, s, e, _) in zip(sink.events, steps):
        mine = {}
        for name, a, b, _ in phases:
            if s <= a and b <= e:
                key = name.split(".", 1)[1]
                mine[key] = mine.get(key, 0.0) + (b - a) * 1e-9
        assert set(mine) == set(ev.phases)
        for key, secs in mine.items():
            assert secs - 1e-5 <= ev.phases[key] <= ev.t_end - ev.t_start


def test_turns_alternate_with_steps(served):
    _, _, spans = served
    loop = [s for s in spans if s[0] in ("engine.step", "frontend.turn")]
    kinds = [s[0] for s in loop]
    last = max(i for i, k in enumerate(kinds) if k == "engine.step")
    # turn, step, turn, step, ..., step, then the turns that drain/close
    assert kinds[:last + 1] == ["frontend.turn", "engine.step"] \
        * ((last + 1) // 2)
    assert set(kinds[last + 1:]) == {"frontend.turn"}
    for (_, _, end, _), (_, start, _, _) in zip(loop, loop[1:]):
        assert end <= start


def test_table_uploads_count_the_table_spans(served):
    _, sink, spans = served
    tables = [s for s in spans if s[0] == "engine.tables"]
    for ev, (_, s, e, _) in zip(sink.events, _steps(spans)):
        assert ev.table_uploads == sum(s <= a and b <= e
                                       for _, a, b, _ in tables)
    assert sum(ev.table_uploads for ev in sink.events) > 0


def test_ledger_carries_phases_uploads_and_inter_token_gaps(served):
    eng, sink, _ = served
    recs = sink.step_records
    assert [r["table_uploads"] for r in recs] \
        == [ev.table_uploads for ev in sink.events]
    for r, ev in zip(recs, sink.events):
        assert r["phases_ms"] == pytest.approx(
            {k: v * 1e3 for k, v in ev.phases.items()})
    snap = sink.snapshot()
    assert set(snap["phases_ms"]) == PHASES
    assert snap["phases_ms"]["first_token"]["n"] == 5
    assert snap["table_uploads"] == sum(r["table_uploads"] for r in recs)
    # one gap per token after a request's first, each from step ends
    assert snap["itl_s"]["n"] == sum(len(r.out_tokens) - 1
                                     for r in eng.completed)
    t_end = {}
    for ev in sink.events:
        for te in ev.tokens:
            t_end.setdefault(te.uid, []).append(ev.t_end)
    gaps = [b - a for ts in t_end.values() for a, b in zip(ts, ts[1:])]
    assert snap["itl_s"]["max"] == pytest.approx(max(gaps))
    assert snap["itl_s"]["min"] == pytest.approx(min(gaps))


def test_pool_occupancy_gauge_skips_the_cache_walk(served):
    eng, sink, _ = served
    assert eng.device_pool_occupancy() \
        == eng.device_pool_stats()["occupancy_per_device"] \
        == [eng.pool.occupancy()]
    assert all(len(r["pool_device_occupancy"]) == 1
               for r in sink.step_records)
