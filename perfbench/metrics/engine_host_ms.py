"""Median over the window's engine steps of the `engine.step` span's time
outside `engine.token_sync` and `engine.first_token`: the engine's host
work in a step, without its waits on the device (`lib/spans.py`)."""
from perfbench.lib import spans


def read(run):
    r = spans.of(run)
    return None if r is None else spans.median(r.engine_host_ms)
