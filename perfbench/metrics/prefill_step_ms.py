"""Median wall of the window's engine steps that ran a prefill chunk
(and the batched decode with it)."""
import numpy as np


def read(run):
    w = [s.t_end - s.t_start for s in run.window_steps
         if s.prefill_chunks > 0]
    return float(np.median(w)) * 1e3 if w else None
