"""Device time of one scan step of the predictor's attention core: the
`run_chunk` ops whose named-scope path passes through ``attention`` (QK^T,
the scale, the softmax and PV of every layer), per device, over the steps
the `run_chunk` executions ran, in us. Moves `sim_instr_per_s`.

An op counts whole when its scope path passes through ``attention``; on
the TPU a fusion's path is its root's, and in tx6's compiled program the
fusions rooted there hold no other scope's work
(`tests/test_tpu_compile.py`). A program without the scope (a predictor
with no attention, or a program from before the scope) gives None."""

SCOPE = "attention"


def read(r):
    if r.trace is None or not r.window.batches:
        return None
    from bench import program_trace, tracing

    chunks = {b.chunk for b in r.window.batches}
    mt = tracing.module_time(r.trace, "run_chunk")
    if len(chunks) != 1 or mt is None or mt[1] == 0:
        return None
    per_device = program_trace.for_cell(r.cell.name)["chunk_ops"][:r.chips]
    lo, hi = r.trace["lo"], r.trace["hi"]
    spans = [min(e, hi) - max(s, lo) for ops in per_device for s, e, scope in ops
             if e > lo and s < hi and SCOPE in program_trace._parts(scope)]
    if not spans:
        return None
    return sum(spans) / len(per_device) / 1e3 / (mt[1] * chunks.pop())
