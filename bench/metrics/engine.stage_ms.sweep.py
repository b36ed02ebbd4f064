"""Host time of a batch's host-to-device puts and chunk enqueues (the
`simnet.stage` spans, the program's `BatchReport.stage_seconds`), the mean
over the window's batches, in ms. Moves `sim_instr_per_s`."""


def read(r):
    from bench import program_trace

    return program_trace.batch_mean_ms(r.window.batches, "stage_seconds")
