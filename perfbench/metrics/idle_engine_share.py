"""Share of the traced window in which the device is idle while an
`engine.step` span is open and neither `engine.token_sync` nor
`engine.first_token` is: the engine's own host work (`lib/spans.py`)."""
from perfbench.lib import spans


def read(run):
    return spans.idle_share(spans.of(run), "engine")
