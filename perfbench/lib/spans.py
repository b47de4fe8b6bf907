"""The program's own spans in the traced window, against the device's idle
time.

The serving stack opens `jax.profiler` spans on the profiler's clock, the
clock of the device operations: `engine.step` (one per engine step, with
its phases `engine.admit`, `engine.prefill_chunk`, `engine.first_token`,
`engine.decode`, `engine.token_sync`, `engine.emit`, `engine.tables`) on
the executor's thread, and `frontend.turn` (the serve loop's work between
two steps) on the event loop's thread. Between the end of a step and the
start of the next turn the step's return is handed back to the loop
("handoff.return"); between the end of a turn and the start of the next
step the step is handed to the executor ("handoff.dispatch").

Each idle interval of the device is split exactly at the spans'
boundaries (not by its midpoint, as `trace.span_at` names a whole gap):

- `frontend`: device idle and no `engine.step` open;
- `engine`: device idle, `engine.step` open, no sync span open;
- `sync`: device idle while `engine.token_sync` or `engine.first_token`
  is open, the host waiting on the device's result.

The three add up to the idle time that `idle_share` reads. A program
without these spans (no `engine.step` in the trace) reads nothing.
"""
from __future__ import annotations

import bisect
import dataclasses
import statistics

from . import trace as trace_mod

PREFIX = ("bench.", "engine.", "frontend.")
STEP, TURN = "engine.step", "frontend.turn"
SYNC = ("engine.token_sync", "engine.first_token")


def union(ivs) -> list:
    """Sorted, merged union of (start, end) intervals."""
    out = []
    for s, e in sorted(ivs):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def overlap(a, b) -> float:
    """Measure of the intersection of two sorted, merged interval lists."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def idle_intervals(ops, window) -> list:
    """The window's intervals in which no operation of one device ran."""
    edges = [window[0]] + [x for iv in trace_mod.busy_intervals(ops, window)
                           for x in iv] + [window[1]]
    return [[s, e] for s, e in zip(edges[0::2], edges[1::2]) if e > s]


def _named(tr, name) -> list:
    return sorted((s, e) for n, s, e in tr.spans if n == name)


@dataclasses.dataclass
class Reading:
    idle_s: dict           # {"frontend", "engine", "sync"} -> seconds
    handoff_ms: list       # per window step: return + dispatch hand-off
    engine_host_ms: list   # per window step: its time outside sync spans
    window_s: float


def read_trace(tr) -> Reading | None:
    """The reading of a `trace.Trace` loaded with the program's spans."""
    steps = _named(tr, STEP)
    if not steps:
        return None
    step_u = union(steps)
    sync_u = union((s, e) for n, s, e in tr.spans if n in SYNC)
    held = union(step_u + sync_u)
    idle = {"frontend": 0.0, "engine": 0.0, "sync": 0.0}
    for ops in tr.devices.values():
        gaps = idle_intervals(ops, tr.window)
        total = sum(e - s for s, e in gaps)
        in_sync, in_held = overlap(gaps, sync_u), overlap(gaps, held)
        idle["sync"] += in_sync
        idle["engine"] += in_held - in_sync
        idle["frontend"] += total - in_held
    n = len(tr.devices)
    idle = {k: v / n * 1e-9 for k, v in idle.items()}

    a, b = tr.window
    turns = _named(tr, TURN)
    turn_starts = [s for s, _ in turns]
    inside = [(s, e) for s, e in steps if s >= a and e <= b]
    host = [((e - s) - overlap([[s, e]], sync_u)) * 1e-6 for s, e in inside]
    handoff = []
    for (_, prev_end), (s, e) in zip(steps, steps[1:]):
        if prev_end < a or e > b:
            continue
        # the one turn between the two steps (two: the loop parked for
        # work in between, which is no hand-off)
        i = bisect.bisect_left(turn_starts, prev_end)
        between = [t for t in turns[i:i + 2] if t[1] <= s]
        if len(between) == 1:
            ts, te = between[0]
            handoff.append(((ts - prev_end) + (s - te)) * 1e-6)
    return Reading(idle, handoff, host, tr.window_s)


def median(xs):
    return float(statistics.median(xs)) if xs else None


def idle_share(reading: Reading | None, part: str):
    if reading is None:
        return None
    return 100.0 * reading.idle_s[part] / reading.window_s


def idle_by_name(tr) -> list:
    """[(name, seconds)] for each piece of device idle time, split at every
    program span's boundary and named by the innermost program span open
    in it, or by the hand-off it falls in ("handoff.return",
    "handoff.dispatch"), else "no span"; adjacent pieces of one idle
    interval with one name are merged. Longest first."""
    prog = [(n, s, e) for n, s, e in tr.spans
            if n.startswith(("engine.", "frontend."))]
    bounds = sorted({x for _, s, e in prog for x in (s, e)})
    loop = sorted((s, e, n) for n, s, e in prog if n in (STEP, TURN))
    loop_ends = sorted((e, n) for s, e, n in loop)
    loop_starts = [(s, n) for s, e, n in loop]
    by_start = sorted(prog, key=lambda p: p[1])
    starts = [p[1] for p in by_start]
    longest = max((e - s for _, s, e in prog), default=0)

    def name_at(t):
        best = None
        lo = bisect.bisect_left(starts, t - longest)
        for n, s, e in by_start[lo:bisect.bisect_right(starts, t)]:
            if s <= t <= e and (best is None or e - s < best[2] - best[1]):
                best = (n, s, e)
        if best is not None:
            return best[0]
        i = bisect.bisect_left(loop_ends, (t,)) - 1
        j = bisect.bisect_right(loop_starts, (t,))
        before = loop_ends[i][1] if i >= 0 else None
        after = loop_starts[j][1] if j < len(loop_starts) else None
        if (before, after) == (STEP, TURN):
            return "handoff.return"
        if (before, after) == (TURN, STEP):
            return "handoff.dispatch"
        return "no span"

    out = []
    for ops in tr.devices.values():
        for s, e in idle_intervals(ops, tr.window):
            cuts = bounds[bisect.bisect_right(bounds, s):
                          bisect.bisect_left(bounds, e)]
            pieces = []
            for lo, hi in zip([s] + cuts, cuts + [e]):
                name = name_at((lo + hi) / 2)
                if pieces and pieces[-1][0] == name:
                    pieces[-1][1] += hi - lo
                else:
                    pieces.append([name, hi - lo])
            out += [(n, d * 1e-9) for n, d in pieces]
    return sorted(out, key=lambda p: -p[1])


_last: list = [None, None]     # (run, Reading) of the last run read


def of(run) -> Reading | None:
    """The reading of a run's traced window, loaded once per run from the
    trace the run left (`bench.TRACE_DIR / <cell>`); None untraced."""
    if run.trace is None:
        return None
    if _last[0] is not run:
        from . import bench
        tr = trace_mod.load(str(bench.TRACE_DIR / run.cell.name),
                            span_prefix=PREFIX)
        _last[:] = [run, read_trace(tr)]
    return _last[1]
