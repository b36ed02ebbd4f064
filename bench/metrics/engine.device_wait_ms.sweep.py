"""Host time blocked on the device once a batch's chunks are enqueued
(the `simnet.device_wait` span, the program's
`BatchReport.device_wait_seconds`): the overlap a pipelined engine would
win back. The mean over the window's batches, in ms. Moves
`sim_instr_per_s`."""


def read(r):
    from bench import program_trace

    return program_trace.batch_mean_ms(r.window.batches, "device_wait_seconds")
