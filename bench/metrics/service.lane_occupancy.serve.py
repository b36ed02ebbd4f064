"""Live lanes over dispatched lanes (dead lanes pad each batch to its
power-of-two bucket), as the change over the window of SimServe's own
`lanes_live` / `lanes_dispatched` counters, in %. Moves `job_p95_ms`."""


def read(r):
    c = r.window.counters
    if not c.get("lanes_dispatched"):
        return None
    return 100.0 * c["lanes_live"] / c["lanes_dispatched"]
