"""Mean host time of one `SimServe.submit` call (featurizing the trace and
queueing the job), timed by the harness's own span around the call, in
ms. Moves `job_p95_ms`."""


def read(r):
    s = r.spans.get("submit")
    if not s:
        return None
    return 1e3 * sum(s) / len(s)
