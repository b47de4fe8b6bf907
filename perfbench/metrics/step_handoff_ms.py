"""Median over the window's engine steps of the time between two steps
that lies outside both the steps and the front end's turn: from the end
of `engine.step` to the start of `frontend.turn` (the step's return to
the event loop), plus from the end of that turn to the start of the next
`engine.step` (its dispatch to the executor). Read from the program's
spans in the traced window (`lib/spans.py`)."""
from perfbench.lib import spans


def read(run):
    r = spans.of(run)
    return None if r is None else spans.median(r.handoff_ms)
