"""Process start to the window's opening: imports, weights and PTQ,
engine, warm-up of every shape, and the ramp until every slot is busy."""


def read(run):
    return run.setup_s
