"""Median wall of the window's engine steps that ran no prefill chunk.
A step ends in a host sync on the sampled tokens, so this is a time to
device completion."""
import numpy as np


def read(run):
    w = [s.t_end - s.t_start for s in run.window_steps
         if s.prefill_chunks == 0 and s.decode_batch > 0]
    return float(np.median(w)) * 1e3 if w else None
