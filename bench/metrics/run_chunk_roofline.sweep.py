"""The chunk program's share of its roofline: the least time of one scan
step (the larger of its FLOPs over the bf16 peak and its bytes over the HBM
bandwidth, `bench/counts.py`) over the measured device time of one scan
step (the `run_chunk` executions in the trace, per device, over the steps
they ran). Moves `sim_instr_per_s`."""


def read(r):
    from bench import counts, tracing

    if r.trace is None or not r.window.batches:
        return None
    lanes = {b.n_lanes for b in r.window.batches}
    chunks = {b.chunk for b in r.window.batches}
    if len(lanes) != 1 or len(chunks) != 1:
        return None  # one shape per window, or no single roofline
    mt = tracing.module_time(r.trace, "run_chunk")
    if mt is None or mt[1] == 0:
        return None
    seconds, executions = mt
    measured = seconds / (executions * chunks.pop())
    least, _ = counts.least_step_seconds(r.step_counts(lanes.pop() // r.chips), r.peak)
    return 100.0 * least / measured
