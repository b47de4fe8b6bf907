"""Mean share of the engine's slots in each window step's batched decode
(`StepEvents.decode_batch / batch_slots`)."""


def read(run):
    steps = run.window_steps
    slots = int(run.cell.traffic["slots"])
    return 100.0 * sum(s.decode_batch for s in steps) / (slots * len(steps))
