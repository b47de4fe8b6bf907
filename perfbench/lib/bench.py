"""One run of one cell: set-up, the measured window, the output check.

The system under test is the program's serving stack as a user runs it:
`repro.launch.serve` builds the engine (`make_engine`, `build_policy`),
`repro.serve.frontend.AsyncFrontend` streams tokens to the benchmark's
closed-loop clients over `repro.serve.engine.ServingEngine`. The
benchmark makes the weights from the seed and hands them to the program's
post-training quantization (`repro.core.qlinear.quantize_params`), both in
one jitted call. Everything else (traffic, metrics, the reference and the
comparison) is the benchmark's own.
"""
from __future__ import annotations

import asyncio
import collections
import dataclasses
import gc
import importlib
import itertools
import math
import sys
import time

import numpy as np

from . import check, traffic as traffic_mod, weights
from .cell import ROOT, Cell
from .model_config import architecture

CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".perfbench_trace"     # the newest trace of each cell
# JAX's events for an executable lowered, compiled or read from the
# persistent cache: none may fall inside the window.
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/compile_requests_use_cache")


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(f"[perfbench] {msg}", file=sys.stderr, flush=True)


def import_program():
    src = ROOT / "src"
    if not (src / "repro" / "__init__.py").is_file():
        raise FileNotFoundError(f"the program is not in this checkout "
                                f"(no {src}/repro)")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    from repro import backends
    from repro.launch import serve
    return backends, serve


def configure_jax() -> None:
    """Persistent compilation cache at a fixed path in the checkout, for
    every program however small, so that only a cell's first run in a
    checkout compiles. No eviction: an evicting cache (which the machine's
    environment may ask for) fails on entries written without one."""
    import jax
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def devices(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX finds "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:chips]


def arch_config(cfg):
    """The program's architecture config with every size the
    configuration's architecture module sets from its file."""
    from repro.configs import get_config
    return dataclasses.replace(get_config(cfg.arch),
                               **architecture(cfg).program_sizes(cfg))


def mesh_plan(cfg, chips: int):
    """The program's device mesh for a cell on `chips` chips: none on one
    chip; otherwise the file's `program.mesh` (data, model) sizes, which
    cover exactly the cell's chips."""
    if chips == 1:
        return None
    if math.prod(cfg.mesh) != chips or len(cfg.mesh) != 2:
        raise ValueError(f"{cfg.name}: a cell on {chips} chips needs a "
                         f"program.mesh of two sizes whose product is "
                         f"{chips}, not {list(cfg.mesh)}")
    from repro.runtime.elastic import MeshPlan
    return MeshPlan(shape=cfg.mesh, axis_names=("data", "model"),
                    dropped_devices=0)


@dataclasses.dataclass
class Step:
    step: int
    t_start: float
    t_end: float
    prefill_chunks: int
    decode_batch: int
    tokens: list           # [(uid, index)] sampled in this step


@dataclasses.dataclass
class Run:
    """What a metric reader may read."""
    cell: Cell
    setup_s: float
    steps: list            # every Step, set-up included
    window: tuple          # (first, last) index into `steps` of the window
    t_open: float
    t_close: float
    prompt_len: dict       # uid -> prompt length
    peaks: dict
    trace: object = None   # lib.trace.Trace of the window (--trace 1)

    @property
    def window_steps(self):
        return self.steps[self.window[0]:self.window[1] + 1]

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open


class Recorder:
    """The front end's metrics sink: keeps each step's events and opens
    and closes the window at step boundaries."""

    def __init__(self, seconds: float, ramp_uids: int, trace_dir):
        self.seconds, self.ramp_uids, self.trace_dir = (seconds, ramp_uids,
                                                        trace_dir)
        self.steps: list = []
        self.first_seen: set = set()
        self.state = "warmup"
        self.t_open = self.t_close = None
        self.window = None
        self.audit_open = None   # engine.trace_audit() at the window's open
        self.closed = asyncio.Event()
        self._span = None

    def on_step(self, ev, engine) -> None:
        self.steps.append(Step(ev.step, ev.t_start, ev.t_end,
                               ev.prefill_chunks, ev.decode_batch,
                               [(t.uid, t.index) for t in ev.tokens]))
        if self.state == "ramp":
            self.first_seen |= {t.uid for t in ev.tokens if t.first}
            if len(self.first_seen) >= self.ramp_uids:
                self.audit_open = engine.trace_audit()
                self._open()
        elif self.state == "window" and ev.t_start >= self.t_open:
            if self.window is None:
                self.window = [len(self.steps) - 1, None]
            if ev.t_end >= self.t_open + self.seconds:
                self.window[1] = len(self.steps) - 1
                self.t_close = ev.t_end
                self._span.__exit__(None, None, None)
                gc.unfreeze()
                self.state = "closed"
                self.closed.set()

    def start_ramp(self) -> None:
        """Open the window once as many requests as there are clients
        have been prefilled, so that every slot is busy when it opens."""
        self.state = "ramp"

    def _open(self) -> None:
        import jax
        # the set-up's garbage (compiles, traces) is collected now and the
        # survivors frozen, so that no full collection over them lands in
        # the window
        gc.collect()
        gc.freeze()
        if self.trace_dir is not None:
            jax.profiler.start_trace(str(self.trace_dir))
        self._span = jax.profiler.TraceAnnotation("bench.window")
        self._span.__enter__()
        self.t_open = time.monotonic()
        self.state = "window"


async def _serve(eng, fe_cls, spec, reqs, warm, rec: Recorder, vocab, seed):
    """Warm up, ramp the closed loop, run the window, stop. Returns
    (finished requests [(uid, prompt, tokens, reason, t_done)], set-up
    phase walls)."""
    import jax
    walls = {}
    fe = fe_cls(eng, metrics=rec)
    fe.start()
    rng = np.random.default_rng([seed, 2])
    t = time.monotonic()
    with jax.profiler.TraceAnnotation("bench.warmup"):
        streams = [fe.submit(rng.integers(0, vocab, n).astype(np.int32), 2)
                   for n in warm]
        for s in streams:
            async for _ in s:
                pass
    walls["warmup"] = time.monotonic() - t
    t = time.monotonic()
    rec.start_ramp()
    finished, nxt, stop = [], itertools.count(), []

    async def client():
        for i in nxt:
            if stop:
                return
            prompt, new = reqs[i % len(reqs)]    # the mix repeats
            with jax.profiler.TraceAnnotation("bench.submit"):
                stream = fe.submit(prompt, new)
            toks = [tok async for tok in stream]
            finished.append((stream.uid, prompt, toks, stream.finish_reason,
                             time.monotonic()))

    clients = [asyncio.ensure_future(client())
               for _ in range(int(spec["clients"]))]
    await rec.closed.wait()
    walls["ramp"] = rec.t_open - t
    stop.append(True)
    for c in clients:
        c.cancel()
    # AsyncFrontend has no abort: cancel its serve loop instead of
    # draining every request still in flight, then wait for the step that
    # runs in the executor
    fe._task.cancel()
    await asyncio.gather(*clients, fe._task, return_exceptions=True)
    await asyncio.get_running_loop().shutdown_default_executor()
    return finished, walls


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_process: float,
        *, require_tpu: bool = True, backend: str | None = None,
        fault=None, controls: dict | None = None) -> dict:
    """One run; returns the result line's object. `t_process` is
    `time.monotonic()` at process start. Tests and `perfbench/control.py`
    only: `fault` breaks the timed path (one of `lib.faults`: it takes
    the engine and changes it); `controls` ({name: matrix product}) adds,
    under "controls", what each lower-precision reference reads on the
    same sample, judged by the same limits."""
    import jax
    backends, serve = import_program()
    devs = devices(cell.chips, require_tpu)
    configure_jax()
    from repro.core.qlinear import quantize_params
    from repro.models.model import build_model
    from repro.serve.frontend import AsyncFrontend
    from . import peaks as peaks_mod
    peaks = peaks_mod.for_kind(devs[0].device_kind) if require_tpu else {}
    cfg, spec = cell.config, cell.traffic
    if spec["loop"] != "closed":
        raise ValueError(f"traffic {spec['name']!r}: only a closed loop "
                         f"is driven, not {spec['loop']!r}")
    arch = arch_config(cfg)
    mesh = mesh_plan(cfg, cell.chips)
    policy = serve.build_policy(arch, cfg.quant, backend=backend or cfg.backend)
    walls = {"imports": time.monotonic() - t_process}

    t = time.monotonic()
    model = build_model(arch, policy, remat=False)
    key = weights.seed_key(seed)
    make = jax.jit(lambda k: quantize_params(
        weights.to_program_tree(cfg, weights.make_weights(cfg, k)), policy))
    params = jax.block_until_ready(make(key))
    walls["weights_ptq"] = time.monotonic() - t
    t = time.monotonic()
    backends.reset_dispatch_stats()
    eng = serve.make_engine(model, params, slots=int(spec["slots"]),
                            max_len=int(spec["max_len"]),
                            page_size=int(spec["page_size"]),
                            prefill_chunk=int(spec["prefill_chunk"]),
                            mesh=mesh, backend=backend or cfg.backend)
    del params
    if fault is not None:
        fault(eng)

    trace_dir = None
    if trace:
        trace_dir = TRACE_DIR / cell.name
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
    rec = Recorder(seconds, int(spec["clients"]), trace_dir)
    step = eng.step

    def traced_step():
        with jax.profiler.TraceAnnotation("bench.engine_step"):
            return step()
    eng.step = traced_step     # the front end calls engine.step each step
    walls["engine"] = time.monotonic() - t
    events = []        # (JAX monitoring event, host time, seconds)
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, d, **kw: events.append((ev, time.monotonic(), d)))
    jax.monitoring.register_event_listener(
        lambda ev, **kw: events.append((ev, time.monotonic(), 0.0)))
    reqs = traffic_mod.requests(spec, cfg.vocab_size, seed)
    warm = traffic_mod.warmup_prompt_lengths(spec)
    finished, serve_walls = asyncio.run(_serve(
        eng, AsyncFrontend, spec, reqs, warm, rec, cfg.vocab_size, seed))
    walls.update(serve_walls)
    audit0, audit1 = rec.audit_open, eng.trace_audit()
    stats = backends.dispatch_stats()
    mem = max(d.memory_stats().get("peak_bytes_in_use", 0) for d in devs) \
        if require_tpu else 0
    tr = None
    if trace:
        jax.profiler.stop_trace()
        from . import trace as trace_mod
        tr = trace_mod.load(str(trace_dir))
    setup_s = rec.t_open - t_process
    log("set-up phases (s): " + ", ".join(f"{k} {v:.3f}"
                                          for k, v in walls.items()))
    prompt_len = {uid: len(p) for uid, p, *_ in finished}
    r = Run(cell, setup_s, rec.steps, tuple(rec.window), rec.t_open,
            rec.t_close, prompt_len, peaks, tr)
    # the in-flight requests' prompt lengths, for the context of their
    # tokens: the engine still holds them
    for req in list(eng.slots) + [p.req for p in
                                  getattr(eng, "_prefilling", [])]:
        if req is not None:
            r.prompt_len[req.uid] = len(req.prompt)
    # free the program's state before the reference runs: its jitted
    # steps hold the engine, and through it the weights and caches
    del eng, rec, step, make
    if mesh is not None:
        backends.configure_mesh(None)
    jax.clear_caches()
    gc.collect()
    in_use = max(d.memory_stats().get("bytes_in_use", 0) for d in devs) \
        if require_tpu else 0

    in_window = [f for f in finished if r.t_open <= f[4] <= r.t_close]
    failed = [f for f in in_window if f[3] != "max_new_tokens"]
    window_events = collections.defaultdict(lambda: [0, 0.0])
    for ev, t, d in events:
        if r.t_open <= t <= r.t_close:
            window_events[ev][0] += 1
            window_events[ev][1] += d
    checks = {
        "declines": (len([k for k in stats if "->" in k]), 0),
        "window_compiles": (
            sum(window_events[ev][0] for ev in COMPILE_EVENTS)
            + sum(audit1[k] - audit0[k] for k in ("prefill_traces",
                                                   "decode_traces")), 0),
    }
    read = check.served_readings(cfg, seed, in_window,
                                 int(spec["check_requests"]),
                                 int(spec["max_len"]))
    checks.update(check.summarize(read, cell.limits))
    correct = bool(in_window) and not failed and check.passes(checks)

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        val = importlib.import_module(
            f"perfbench.metrics.{m['name']}").read(r)
        if val is not None:
            metrics[m["name"]] = {"value": val, "unit": m["unit"]}
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": int(mem)}
    out = {"correct": bool(correct), "attempted": len(in_window),
           "failed": len(failed), "metrics": metrics, "device": dev}
    if tr is not None:
        from . import trace as trace_mod
        dev["busy_s"] = trace_mod.busy_s(tr)
        dev["window_s"] = tr.window_s
        out["breakdown"] = {
            "device_ops": [[n, s] for n, s in trace_mod.op_seconds(tr)[:10]],
            "idle_gaps": [[n, s] for n, s in trace_mod.idle_gaps(tr)[:10]]}
    seqs = [np.concatenate([p[-1:], np.asarray(t, np.int32)])
            for _, p, t, _, _ in in_window]
    out["stats"] = {"dispatch": stats, "window_steps": len(r.window_steps),
                    "longest_step_s": max(st.t_end - st.t_start
                                          for st in r.window_steps),
                    "bytes_in_use_before_check": int(in_use),
                    "repeat_share": float(
                        sum(int(np.sum(q[1:] == q[:-1])) for q in seqs)
                        / max(1, sum(len(q) - 1 for q in seqs))),
                    "setup_walls": walls,
                    "sampled_tokens": int(read["rank"].size),
                    **check.gap_stats(read),
                    "window_events": {k: window_events[k]
                                      for k in sorted(window_events)}}
    if controls:
        # each control through the same comparison and limits as the
        # program: it has to come out not correct
        out["controls"] = {}
        for name, mm in controls.items():
            cr = check.served_readings(cfg, seed, in_window,
                                       int(spec["check_requests"]),
                                       int(spec["max_len"]), mm=mm)
            c = check.summarize(cr, cell.limits)
            out["controls"][name] = {
                "correct": check.passes(c),
                "checks": {k: {"value": v, "limit": lim}
                           for k, (v, lim) in c.items()},
                **check.gap_stats(cr)}
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    return out
