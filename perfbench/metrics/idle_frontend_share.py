"""Share of the traced window in which the device is idle and no
`engine.step` span is open: the front end's turn and the hand-offs of the
step between the event loop and the executor (`lib/spans.py`)."""
from perfbench.lib import spans


def read(run):
    return spans.idle_share(spans.of(run), "frontend")
