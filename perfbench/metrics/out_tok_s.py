"""Output tokens generated in the window over the window's length
(host clock; the window is whole engine steps)."""


def read(run):
    return sum(len(s.tokens) for s in run.window_steps) / run.window_s
