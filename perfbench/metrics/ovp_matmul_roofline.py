"""The fused OVP matmul's least time over its measured device time, in
the traced window. Least time per call is the larger of its operations
over the int8 peak (the work is W4A4 codes) and its bytes over the HBM
bandwidth, from the call shapes (`perfbench/lib/cost.py`): every window
step runs one batched decode of all slots' rows and, where it has one, a
prefill chunk, each through every layer's seven projections. Events are
found by the kernel's name in the trace; where their count is not the
number of calls the steps made, nothing is read."""
from perfbench.lib import cost, trace

# the fused matmul's pallas_call, under its jitted wrapper in kernels/ops.py
KERNEL_NAMES = ("_fused_padded",)


def read(run):
    if run.trace is None or not run.peaks:
        return None
    cfg, spec = run.cell.config, run.cell.traffic
    calls = []
    for s in run.window_steps:
        if s.decode_batch:
            calls += cost.step_ovp_matmuls(cfg, int(spec["slots"]))
        if s.prefill_chunks:
            calls += cost.step_ovp_matmuls(cfg, int(spec["prefill_chunk"]))
    events = trace.matching(run.trace, KERNEL_NAMES)
    if not events or len(events) != len(calls):
        return None
    least = sum(cost.least_time_s(*cost.ovp_matmul(m, k, n),
                                  run.peaks["int8_ops_per_s"],
                                  run.peaks["hbm_bytes_per_s"])
                for m, k, n in calls)
    return 100.0 * least / (sum(e.end - e.start for e in events) * 1e-9)
