"""Reduction of a JAX profiler trace to the numbers the per-layer metrics
read: device busy time, idle gaps by the host span open in them, and
device time by operation name.

The trace is read with JAX alone (`jax.profiler.ProfileData`). Device
operations are the events of the `XLA Ops` line of each `/device:TPU:<n>`
plane; host spans are the `jax.profiler.TraceAnnotation`s the benchmark
opens, found by name on the host plane. All times are nanoseconds on the
profiler's common clock.
"""
from __future__ import annotations

import dataclasses
import glob
from pathlib import Path

WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Op:
    name: str
    start: float
    end: float


@dataclasses.dataclass
class Trace:
    devices: dict          # device plane name -> [Op] sorted by start
    spans: list            # [(name, start, end)] of the benchmark's spans
    window: tuple          # (start, end) of the measured window

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def from_events(devices: dict, spans: list) -> Trace:
    """A `Trace` from plain events: `devices` maps a device name to
    [(instruction name, start_ns, duration_ns)], `spans` is
    [(name, start_ns, duration_ns)]; the window is the `bench.window`
    span."""
    win = [(s, s + d) for n, s, d in spans if n == WINDOW_SPAN]
    if len(win) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN!r} span, found "
                         f"{len(win)}")
    ops = {dev: sorted((Op(n, s, s + d) for n, s, d in evs),
                       key=lambda o: o.start)
           for dev, evs in devices.items()}
    return Trace(ops, [(n, s, s + d) for n, s, d in spans
                       if n != WINDOW_SPAN], win[0])


def load(profile_dir: str, span_prefix: str = "bench.") -> Trace:
    """Read the one `.xplane.pb` under `profile_dir`."""
    from jax.profiler import ProfileData
    files = glob.glob(str(Path(profile_dir) / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    if len(files) != 1:
        raise FileNotFoundError(f"expected one xplane file under "
                                f"{profile_dir}, found {files}")
    data = ProfileData.from_file(files[0])
    devices, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[plane.name] = [
                        (op_name(e.name), e.start_ns, e.duration_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.duration_ns)
                          for e in line.events
                          if e.name.startswith(span_prefix)]
    if not devices:
        raise ValueError("the trace holds no TPU device plane with an "
                         "'XLA Ops' line")
    return from_events(devices, spans)


def op_name(text: str) -> str:
    """The HLO instruction's name out of the event's name, which on a TPU
    is the whole instruction (`%name = type op(operands), ...`)."""
    return text.split(" = ", 1)[0].lstrip("%")


# control flow whose events span the operations they run
CONTAINERS = ("while", "conditional", "call")


def _clip(ops, window):
    a, b = window
    return [(max(o.start, a), min(o.end, b)) for o in ops
            if o.end > a and o.start < b]


def busy_intervals(ops, window) -> list:
    """Union of the operations' intervals inside the window, merged."""
    out = []
    for s, e in sorted(_clip(ops, window)):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_s(trace: Trace) -> float:
    """Seconds in which some operation ran, averaged over the devices."""
    tot = [sum(e - s for s, e in busy_intervals(ops, trace.window))
           for ops in trace.devices.values()]
    return sum(tot) / len(tot) * 1e-9


def idle_gaps(trace: Trace) -> list:
    """[(host span open at the gap's middle, seconds)] for every gap
    between device operations in the window, over all devices, longest
    first. A gap outside every span is attributed to 'no span'."""
    out = []
    for ops in trace.devices.values():
        busy = busy_intervals(ops, trace.window)
        edges = [trace.window[0]] + [x for iv in busy for x in iv] \
            + [trace.window[1]]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                out.append((span_at(trace.spans, (s + e) / 2),
                            (e - s) * 1e-9))
    return sorted(out, key=lambda g: -g[1])


def span_at(spans, t) -> str:
    """The innermost benchmark span open at time t."""
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "no span"


def op_seconds(trace: Trace) -> list:
    """[(operation name, seconds inside the window)] over all devices,
    largest first; control flow that spans other operations is left out."""
    tot = {}
    for ops in trace.devices.values():
        ops = [o for o in ops if o.end > trace.window[0]
               and o.start < trace.window[1]
               and o.name.split(".")[0] not in CONTAINERS]
        for o, (s, e) in zip(ops, _clip(ops, trace.window)):
            tot[o.name] = tot.get(o.name, 0.0) + (e - s) * 1e-9
    return sorted(tot.items(), key=lambda kv: -kv[1])


def matching(trace: Trace, prefixes) -> list:
    """Operations in the window whose instruction name starts with one of
    `prefixes` (a kernel's `pallas_call` shows under the name of the jitted
    function that wraps it, with a numeric suffix)."""
    return [o for ops in trace.devices.values() for o in ops
            if o.end > trace.window[0] and o.start < trace.window[1]
            and o.name.startswith(tuple(prefixes))]
