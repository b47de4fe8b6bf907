"""Asyncio streaming serve front end over `ServingEngine`.

The engine's `step()` is synchronous and batched; this module is the
control plane that turns it into a service: continuous request intake,
per-request TOKEN STREAMS (an async iterator that yields each token the
engine step it was sampled — the prefill token included), and step-level
SLO observability through a `serve.metrics.MetricsLedger`. Admission
control is the engine's own: paged mode reserves a request's worst-case
page budget all-or-nothing before it leaves the queue (`PagePool`
grants; see docs/kv_cache.md), so the front end never admits what the
pool cannot finish.

    engine = ServingEngine(model, params, EngineCfg(...))
    ledger = MetricsLedger()
    async with AsyncFrontend(engine, metrics=ledger) as fe:
        stream = fe.submit(prompt, max_new_tokens=32)
        async for tok in stream:          # yields the step it's sampled
            print(tok)
    print(ledger.snapshot()["ttft_s"])    # TTFT distribution

Design notes (docs/serving.md has the full architecture):

- ONE serve-loop task drives the engine. Each iteration flushes intake
  into the engine queue, runs `engine.step()` in the default thread-pool
  executor (the event loop stays responsive while the device works, so
  consumers drain their streams *during* a step), then publishes the
  returned `StepEvents` to the streams and the ledger. The engine is
  only ever touched from the loop task — submissions buffer in
  `_intake` and join the queue at the next step boundary, so no lock
  guards the engine and a mid-step `submit()` never races admission.
- Token order within one stream is sampling order (the engine appends
  to `Request.out_tokens` in step order and events mirror that list);
  a stream finishes — `finish_reason` set, iteration stops — strictly
  after its last token is yielded.
- When the engine drains, the loop parks on an event instead of
  busy-polling; `submit()` wakes it. `drain()` awaits the parked state.
- The loop's own work between two steps (publish, intake, the work
  check) is the profiler span `frontend.turn`; the step is
  `engine.step` (docs/serving.md, "Profiler spans").
"""
from __future__ import annotations

import asyncio
import collections
from typing import Deque, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.serve.engine import ServingEngine, StepEvents

_DONE = object()    # stream sentinel: terminal marker after the last token


class TokenStream:
    """One request's async token stream.

    `async for tok in stream` yields each sampled token (ints) in
    sampling order and stops after the terminal token; `finish_reason`
    ("eos" / "max_new_tokens" / "length_cap") is set before the
    iteration ends. `tokens` accumulates everything yielded so far,
    `uid` is assigned when the request enters the engine queue (the
    next step boundary after `submit`), and `queue_position` is the
    submission index on this front end (0-based).
    """

    def __init__(self, queue_position: int):
        self.uid: Optional[int] = None
        self.queue_position = queue_position
        self.tokens: List[int] = []
        self.finish_reason: Optional[str] = None
        self.done = False
        self._q: asyncio.Queue = asyncio.Queue()

    def __aiter__(self) -> "TokenStream":
        return self

    async def __anext__(self) -> int:
        if self.done and self._q.empty():
            raise StopAsyncIteration
        item = await self._q.get()
        if item is _DONE:
            self.done = True
            raise StopAsyncIteration
        return item


class AsyncFrontend:
    """Async serving shell: continuous intake, streaming, SLO metrics.

    Use as an async context manager (`async with AsyncFrontend(...)`),
    or call `start()` from a running event loop and `aclose()` when
    done. `aclose()` finishes all in-flight and queued work first —
    closing is a drain, never an abort.
    """

    def __init__(self, engine: ServingEngine,
                 metrics: Optional[object] = None):
        self.engine = engine
        self.metrics = metrics
        self._intake: Deque[Tuple[TokenStream, np.ndarray, int]] = \
            collections.deque()
        self._streams: Dict[int, TokenStream] = {}
        self._submitted = 0
        self._task: Optional[asyncio.Task] = None
        self._closing = False
        self._wake: Optional[asyncio.Event] = None
        self._idle: Optional[asyncio.Event] = None

    # ----------------------------------------------------------- lifecycle
    def start(self) -> None:
        """Start the serve-loop task on the running event loop."""
        if self._task is not None:
            raise RuntimeError("AsyncFrontend already started")
        loop = asyncio.get_running_loop()
        self._wake = asyncio.Event()
        self._idle = asyncio.Event()
        self._idle.set()
        self._task = loop.create_task(self._serve_loop(),
                                      name="repro-serve-loop")

    async def __aenter__(self) -> "AsyncFrontend":
        self.start()
        return self

    async def __aexit__(self, *exc) -> None:
        await self.aclose()

    async def aclose(self) -> None:
        """Drain remaining work, then stop the serve loop. Re-raises any
        engine error the loop died on."""
        if self._task is None:
            return
        self._closing = True
        self._wake.set()
        try:
            await self._task
        finally:
            self._task = None

    async def drain(self) -> None:
        """Wait until no request is queued, prefilling, or decoding.
        Streams submitted before this call are complete when it
        returns; the front end stays open for more submissions."""
        self._require_running()
        await self._idle.wait()

    # ------------------------------------------------------------- intake
    def submit(self, prompt, max_new_tokens: int = 16) -> TokenStream:
        """Queue one request; returns its `TokenStream` immediately.

        The request joins the engine queue at the next step boundary
        (admission — including the paged all-or-nothing page
        reservation — is the engine's, exactly as in the drained loop).
        Synchronous and loop-thread-only, like all front-end methods.
        """
        self._require_running()
        if self._closing:
            raise RuntimeError("AsyncFrontend is closing")
        stream = TokenStream(queue_position=self._submitted)
        self._submitted += 1
        self._intake.append((stream, np.asarray(prompt, np.int32),
                             max_new_tokens))
        self._idle.clear()
        self._wake.set()
        return stream

    @property
    def completed(self):
        """Completed `Request`s, in completion order (engine-owned)."""
        return self.engine.completed

    # --------------------------------------------------------- serve loop
    def _require_running(self) -> None:
        if self._task is None:
            raise RuntimeError(
                "AsyncFrontend is not running: use `async with "
                "AsyncFrontend(engine) as fe:` or call start() first")
        if self._task.done():
            # surface a crashed serve loop at the call site instead of
            # hanging the caller on a stream that will never finish
            self._task.result()
            raise RuntimeError("AsyncFrontend serve loop has exited")

    def _flush_intake(self) -> None:
        """Move buffered submissions into the engine queue (loop task
        only — the single engine-touching thread)."""
        while self._intake:
            stream, prompt, max_new = self._intake.popleft()
            stream.uid = self.engine.submit(prompt, max_new)
            self._streams[stream.uid] = stream

    def _has_work(self) -> bool:
        return bool(self._intake) or self.engine.has_work()

    async def _serve_loop(self) -> None:
        loop = asyncio.get_running_loop()
        ev: Optional[StepEvents] = None
        while True:
            # `frontend.turn`: the loop's work between the return of one
            # engine step and the dispatch of the next (or the park); the
            # executor's two hand-offs and the wait for work lie outside
            with jax.profiler.TraceAnnotation("frontend.turn"):
                if ev is not None:
                    self._publish(ev)
                    ev = None
                self._flush_intake()
                work = self._has_work()
            if not work:
                self._idle.set()
                if self._closing:
                    return
                self._wake.clear()
                await self._wake.wait()
                continue
            self._idle.clear()
            # the blocking jitted step runs off-loop so stream consumers
            # and new submissions stay live while the device works
            ev = await loop.run_in_executor(None, self.engine.step)

    def _publish(self, ev: StepEvents) -> None:
        """Fan one step's token events out to their streams and the
        metrics ledger — the only consumer of `StepEvents` here."""
        for te in ev.tokens:
            stream = self._streams.get(te.uid)
            if stream is None:
                continue    # submitted directly on the engine: no stream
            stream.tokens.append(te.token)
            stream._q.put_nowait(te.token)
            if te.done:
                stream.finish_reason = te.finish_reason
                stream._q.put_nowait(_DONE)
        if self.metrics is not None:
            self.metrics.on_step(ev, self.engine)
