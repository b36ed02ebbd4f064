"""Device time of one scan step of the chunk program outside the
predictor: the `run_chunk` ops that lie outside the `trunk` and `head`
named scopes (assembly, retire, the ring's copies, unscoped loop ops),
each op that holds other ops (the scan's `while`) counted only through
them, per device, over the steps the `run_chunk` executions ran, in us.
Moves `sim_instr_per_s`."""


def read(r):
    if r.trace is None or not r.window.batches:
        return None
    from bench import program_trace, tracing

    chunks = {b.chunk for b in r.window.batches}
    mt = tracing.module_time(r.trace, "run_chunk")
    if len(chunks) != 1 or mt is None or mt[1] == 0:
        return None
    per_device = program_trace.for_cell(r.cell.name)["chunk_ops"][:r.chips]
    if not per_device or not any(program_trace.is_scoped(scope)
                                 for ops in per_device for _, _, scope in ops):
        return None  # a program without the named scopes
    lo, hi = r.trace["lo"], r.trace["hi"]
    ns = sum(min(e, hi) - max(s, lo) for ops in per_device for s, e, scope in ops
             if e > lo and s < hi and not program_trace.is_model_op(scope))
    return ns / len(per_device) / 1e3 / (mt[1] * chunks.pop())
