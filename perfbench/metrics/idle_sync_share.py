"""Share of the traced window in which the device is idle while
`engine.token_sync` or `engine.first_token` is open: the host waits on a
device result and the device has nothing queued (`lib/spans.py`). With
`idle_frontend_share` and `idle_engine_share` it partitions `idle_share`."""
from perfbench.lib import spans


def read(run):
    return spans.idle_share(spans.of(run), "sync")
