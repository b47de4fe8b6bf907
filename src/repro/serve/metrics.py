"""Step-level serving observability: the TTFT/TPOT SLO ledger.

`MetricsLedger.on_step(events, engine)` consumes the `StepEvents` that
`ServingEngine.step()` returns — the same events the async streaming
front end publishes tokens from — and accumulates two record streams:

  step records     — one per engine step: wall time, queue depth, batch
                     occupancy, decode batch size, prefill-chunk
                     interleaving, page-pool occupancy/fragmentation
                     gauges (paged mode), the host milliseconds of each
                     engine phase (`phases_ms`) and the block-table
                     uploads (`table_uploads`), and the per-step *delta*
                     of `backends.dispatch_stats()` (so fused-vs-fallback
                     attribution lands on the step that traced it).
  request records  — one per completed request: TTFT, TPOT, end-to-end
                     latency, token count, finish reason, queue position.

Metric vocabulary (canonical definitions — docs/serving.md quotes this
table; all times are `time.monotonic()` seconds):

| metric             | definition                                        |
|--------------------|---------------------------------------------------|
| `ttft_s`           | time to first token: `t_first - t_submit` (the   |
|                    | prefill token's sample time minus submission)     |
| `tpot_s`           | time per output token after the first:            |
|                    | `(t_done - t_first) / (n_tokens - 1)`; absent     |
|                    | (`None`) for single-token requests. A per-request |
|                    | mean: it hides the tail that `itl_s` shows        |
| `itl_s`            | inter-token latency: every gap between a          |
|                    | request's consecutive tokens, a token's time      |
|                    | being its step's `StepEvents.t_end`               |
| `latency_s`        | end-to-end: `t_done - t_submit`                   |
| `queue_depth`      | requests waiting in the engine queue AFTER a step |
| `batch_occupancy`  | decode batch size / `batch_slots` for the step    |
| `pool_occupancy`   | `PagePool` used/total pages after the step        |
| `pool_fragmentation` | free fraction of the pool's live span (the      |
|                    | holes `defrag()` would compact)                   |
| `pool_device_occupancy` | per-device pool-occupancy gauge (list, one  |
|                    | entry per "model"-axis shard of the installed     |
|                    | mesh; `[occupancy]` when unsharded) — see         |
|                    | docs/sharding.md                                  |
| `prefill_interleave_ratio` | of steps that ran a prefill chunk, the    |
|                    | fraction that also decoded a non-empty batch      |
|                    | (1.0 = chunked prefill never stalled decode)      |
| `dispatch` / `fallbacks` | folded `backends.dispatch_stats()` deltas:  |
|                    | keys per backends/base.py; `fallbacks` sums every |
|                    | `"->fallback:"` key (quantized serving wants 0)   |
| `phases_ms`        | host milliseconds of each engine phase in the     |
|                    | step (`StepEvents.phases`, the `engine.<phase>`   |
|                    | profiler spans); the summary gives one            |
|                    | distribution per phase, over the steps that ran it|
| `table_uploads`    | block-table uploads in the step (paged mode; one  |
|                    | `engine.tables` span each)                        |

Distributions (`_dist`) report `n/mean/p50/p95/min/max`.

The JSONL trace (`write_jsonl`) is the exchange format the benchmarks
consume (`benchmarks/kernels_bench.py` serve-latency section,
`benchmarks/speedup.py`): one JSON object per line, discriminated by
`"kind"` — `"meta"`, then `"step"` and `"request"` records in emission
order, then one `"summary"` (the `snapshot()` dict). `load_trace` reads
it back grouped by kind.
"""
from __future__ import annotations

import collections
import json
from typing import Dict, List, Optional

import numpy as np

from repro import backends
from repro.serve.engine import ServingEngine, StepEvents


def _dist(xs: List[Optional[float]]) -> Dict[str, float]:
    """n/mean/p50/p95/min/max of the non-None entries ({"n": 0} when
    nothing survives) — the distribution shape every summary metric
    uses."""
    vals = [x for x in xs if x is not None]
    if not vals:
        return {"n": 0}
    a = np.asarray(vals, dtype=np.float64)
    return {"n": int(a.size), "mean": float(a.mean()),
            "p50": float(np.percentile(a, 50)),
            "p95": float(np.percentile(a, 95)),
            "min": float(a.min()), "max": float(a.max())}


class MetricsLedger:
    """Accumulates step + request records from `StepEvents` (see the
    module docstring for the metric vocabulary).

    One ledger serves one engine run — feed it either through
    `run_until_drained(metrics=...)` or an `AsyncFrontend(metrics=...)`;
    both call `on_step` with identical events, so traces from the two
    loops are directly comparable (the golden test in
    tests/test_serve_frontend.py relies on it).
    """

    def __init__(self):
        self.step_records: List[dict] = []
        self.request_records: List[dict] = []
        self.meta: Optional[dict] = None
        self._t0: Optional[float] = None
        self._completed_seen = 0
        # dispatch stats are process-global trace-time counters; deltas
        # attribute them to the step whose jit trace recorded them
        self._last_dispatch = collections.Counter(backends.dispatch_stats())
        self._dispatch_total: collections.Counter = collections.Counter()
        self._last_token_t: Dict[int, float] = {}   # uid -> its last token
        self._itl: List[float] = []

    # ---------------------------------------------------------- recording
    def _capture_meta(self, engine: ServingEngine) -> dict:
        cfg = engine.cfg
        meta = {"kind": "meta", "batch_slots": cfg.batch_slots,
                "max_len": cfg.max_len, "paged": engine.paged,
                "prefill_chunk": cfg.prefill_chunk}
        if engine.paged:
            meta["page_size"] = engine.pool.page_size
            meta["n_pages"] = engine.pool.n_pages
        return meta

    def on_step(self, ev: StepEvents, engine: ServingEngine) -> dict:
        """Record one step's events; returns the step record dict."""
        if self.meta is None:
            self.meta = self._capture_meta(engine)
        if self._t0 is None:
            self._t0 = ev.t_start
        cur = collections.Counter(backends.dispatch_stats())
        delta = cur - self._last_dispatch
        self._last_dispatch = cur
        self._dispatch_total += delta
        rec = {
            "kind": "step",
            "step": ev.step,
            "t_s": ev.t_end - self._t0,
            "dt_s": ev.t_end - ev.t_start,
            "admitted": list(ev.admitted),
            "prefill_chunks": ev.prefill_chunks,
            "decode_batch": ev.decode_batch,
            "batch_occupancy": ev.decode_batch / engine.cfg.batch_slots,
            "tokens": len(ev.tokens),
            "first_tokens": sum(1 for t in ev.tokens if t.first),
            "completed": [t.uid for t in ev.tokens if t.done],
            "queue_depth": ev.queue_depth,
            "active": ev.active,
            "prefilling": ev.prefilling,
            "phases_ms": {k: v * 1e3 for k, v in ev.phases.items()},
            "table_uploads": ev.table_uploads,
        }
        for te in ev.tokens:
            last = self._last_token_t.pop(te.uid, None)
            if last is not None:
                self._itl.append(ev.t_end - last)
            if not te.done:
                self._last_token_t[te.uid] = ev.t_end
        if engine.paged:
            pool = engine.pool
            rec["pool_occupancy"] = pool.occupancy()
            rec["pool_used_pages"] = pool.used_pages
            rec["pool_fragmentation"] = pool.fragmentation()
            rec["pool_alloc_failures"] = pool.alloc_failures
            # per-device pool-occupancy gauge: under a sharded mesh each
            # "model"-axis shard holds 1/tp of the pool bytes at the SAME
            # page occupancy (pages allocate globally)
            rec["pool_device_occupancy"] = engine.device_pool_occupancy()
        if delta:
            rec["dispatch"] = dict(delta)
        self.step_records.append(rec)
        # harvest newly completed requests (engine.completed only grows)
        for req in engine.completed[self._completed_seen:]:
            n = len(req.out_tokens)
            self.request_records.append({
                "kind": "request",
                "uid": req.uid,
                "n_tokens": n,
                "finish_reason": req.finish_reason,
                "ttft_s": req.t_first - req.t_submit,
                "tpot_s": ((req.t_done - req.t_first) / (n - 1)
                           if n > 1 else None),
                "latency_s": req.t_done - req.t_submit,
            })
        self._completed_seen = len(engine.completed)
        return rec

    # ------------------------------------------------------------ exports
    def snapshot(self) -> dict:
        """Structured summary of everything recorded so far (the
        `"summary"` JSONL record): request-level TTFT/TPOT/latency
        distributions, the inter-token gaps, step-level queue/occupancy
        distributions, the chunked-prefill interleave ratio, the folded
        dispatch ledger with its fallback total, and per engine phase
        the distribution of its host milliseconds."""
        steps = self.step_records
        reqs = self.request_records
        phases = sorted({k for r in steps for k in r["phases_ms"]})
        chunk_steps = [r for r in steps if r["prefill_chunks"] > 0]
        interleaved = [r for r in chunk_steps if r["decode_batch"] > 0]
        fallbacks = sum(v for k, v in self._dispatch_total.items()
                        if "->fallback:" in k)
        snap = {
            "kind": "summary",
            "steps": len(steps),
            "requests": len(reqs),
            "tokens": sum(r["tokens"] for r in steps),
            "wall_s": steps[-1]["t_s"] if steps else 0.0,
            "ttft_s": _dist([r["ttft_s"] for r in reqs]),
            "tpot_s": _dist([r["tpot_s"] for r in reqs]),
            "itl_s": _dist(self._itl),
            "latency_s": _dist([r["latency_s"] for r in reqs]),
            "queue_depth": _dist([r["queue_depth"] for r in steps]),
            "batch_occupancy": _dist([r["batch_occupancy"]
                                      for r in steps]),
            "prefill_chunk_steps": len(chunk_steps),
            "interleaved_steps": len(interleaved),
            "prefill_interleave_ratio": (
                len(interleaved) / len(chunk_steps) if chunk_steps
                else None),
            "finish_reasons": dict(collections.Counter(
                r["finish_reason"] for r in reqs)),
            "dispatch": dict(self._dispatch_total),
            "fallbacks": fallbacks,
            "phases_ms": {k: _dist([r["phases_ms"].get(k) for r in steps])
                          for k in phases},
            "table_uploads": sum(r["table_uploads"] for r in steps),
        }
        if steps and "pool_occupancy" in steps[0]:
            snap["pool_occupancy"] = _dist(
                [r.get("pool_occupancy") for r in steps])
            snap["pool_fragmentation"] = _dist(
                [r.get("pool_fragmentation") for r in steps])
        if steps and "pool_device_occupancy" in steps[0]:
            per_dev = [r.get("pool_device_occupancy") or [] for r in steps]
            snap["pool_device_occupancy"] = {
                "n_devices": max((len(p) for p in per_dev), default=0),
                "peak": max((max(p) for p in per_dev if p), default=0.0),
                "final": (per_dev[-1] if per_dev and per_dev[-1]
                          else []),
            }
        return snap

    def write_jsonl(self, path: str) -> None:
        """Write the trace: meta line, then step/request records in
        emission order, then one summary line (`snapshot()`)."""
        with open(path, "w") as f:
            if self.meta is not None:
                f.write(json.dumps(self.meta) + "\n")
            for rec in self.step_records:
                f.write(json.dumps(rec) + "\n")
            for rec in self.request_records:
                f.write(json.dumps(rec) + "\n")
            f.write(json.dumps(self.snapshot()) + "\n")


def load_trace(path: str) -> Dict[str, object]:
    """Read a `write_jsonl` trace back, grouped by record kind:
    `{"meta": dict|None, "steps": [...], "requests": [...],
    "summary": dict|None}` — what the benchmarks consume."""
    out = {"meta": None, "steps": [], "requests": [], "summary": None}
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            rec = json.loads(line)
            kind = rec.get("kind")
            if kind == "meta":
                out["meta"] = rec
            elif kind == "step":
                out["steps"].append(rec)
            elif kind == "request":
                out["requests"].append(rec)
            elif kind == "summary":
                out["summary"] = rec
    return out
