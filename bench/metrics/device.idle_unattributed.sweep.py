"""Share of the window in which device 0 is idle and no host phase of the
program (a `simnet.featurize`, `pack`, `executable`, `stage`,
`device_wait` or `results` span, on any host thread) is open: the idle
time the program's spans do not explain, in %. Moves
`sim_instr_per_s`."""


def read(r):
    if r.trace is None:
        return None
    from bench import program_trace, tracing

    spans = [(s, e) for name, s, e, _ in program_trace.for_cell(r.cell.name)["host"]
             if name in program_trace.LEAF_SPANS]
    if not spans:
        return None
    lo, hi = r.trace["lo"], r.trace["hi"]
    explained = tracing.covered(tracing.clip(r.trace["devices"][0]["busy"] + spans, lo, hi))
    return 100.0 * (1.0 - explained / r.trace["window_ns"])
