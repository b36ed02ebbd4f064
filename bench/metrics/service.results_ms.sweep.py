"""Host time of a batch's results: the copy of the totals to the host,
the numeric guard and each job's `WorkloadResult` (the `simnet.results`
spans, the program's `BatchReport.results_seconds`), the mean over the
window's batches, in ms. Moves `sim_instr_per_s`."""


def read(r):
    from bench import program_trace

    return program_trace.batch_mean_ms(r.window.batches, "results_seconds")
