"""Host time of packing a batch (`pack_workloads` and lane padding, the
`simnet.pack` span, the program's `BatchReport.pack_seconds`), the mean
over the window's batches, in ms. Moves `sim_instr_per_s`."""


def read(r):
    from bench import program_trace

    return program_trace.batch_mean_ms(r.window.batches, "pack_seconds")
