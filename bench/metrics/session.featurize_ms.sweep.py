"""Host time of featurizing a batch's traces at `SimServe.submit` (the
`simnet.featurize` spans, summed per batch into the program's
`BatchReport.featurize_seconds`), the mean over the window's batches, in
ms. Moves `sim_instr_per_s`."""


def read(r):
    from bench import program_trace

    return program_trace.batch_mean_ms(r.window.batches, "featurize_seconds")
