"""The engine's host preparation per batch before its first chunk runs
(pack, lane padding, executable lookup, staging): the mean over the
window's batches of `first_call_seconds - seconds` from the program's own
`BatchReport`s, in ms. Moves `job_p95_ms`."""


def read(r):
    b = r.window.batches
    if not b:
        return None
    return 1e3 * sum(x.first_call_seconds - x.seconds for x in b) / len(b)
