"""Gap between consecutive output tokens, p95 over all gaps of all
requests (host clock); read where the cell is saturated."""


def read(run):
    return run.e2e.get("itl_p95_ms.sat")
