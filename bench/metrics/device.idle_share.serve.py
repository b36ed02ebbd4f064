"""Share of the window in which no operation ran on the device (the mean
over the chips used), from the profiler trace; moves `job_p95_ms`."""


def read(r):
    if r.trace is None:
        return None
    from bench import tracing

    busy_ns = sum(d["busy_ns"] for d in r.trace["devices"]) / len(r.trace["devices"])
    return 100.0 * tracing.idle_share(busy_ns, r.trace["window_ns"])
