"""Time to first token, p95 over all requests due in the window, from
each request's due time (host clock); read where the cell is saturated."""


def read(run):
    return run.e2e.get("ttft_p95_ms.sat")
