"""95th percentile of every gap between consecutive output tokens of a
request, over all requests, for the gaps that end in the window. A token's
time is the end of the engine step that sampled it (`StepEvents.t_end`)."""
import numpy as np


def gaps_s(run) -> list:
    last, out = {}, []
    lo, hi = run.window
    for i, s in enumerate(run.steps[:hi + 1]):
        for uid, _ in s.tokens:
            if i >= lo and uid in last:
                out.append(s.t_end - last[uid])
            last[uid] = s.t_end
    return out


def read(run):
    g = gaps_s(run)
    return float(np.percentile(g, 95)) * 1e3 if g else None
