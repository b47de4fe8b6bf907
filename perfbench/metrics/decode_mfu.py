"""Model operations per generated token, times the traced run's output
tokens per second, over the chip's bf16 peak. Operations come from the
configuration's shapes (2 per weight of every projection and the LM head,
plus attention over the mean context of the window's tokens), so the
number reads the same work whatever implements it."""
from perfbench.lib import cost
from perfbench.metrics import out_tok_s


def read(run):
    ctx = [run.prompt_len[uid] + idx for s in run.window_steps
           for uid, idx in s.tokens if uid in run.prompt_len]
    if not ctx or not run.peaks:
        return None
    flops = cost.flops_per_token(run.cell.config, sum(ctx) / len(ctx))
    return 100.0 * flops * out_tok_s.read(run) \
        / run.peaks["bf16_flops_per_s"]
