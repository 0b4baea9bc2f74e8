"""95th percentile of request latency, from each request's due time to its
reply, over every request due in the window. A failed or refused request
counts as never answered, so more than 5% of them leave no number."""
from harness.stats import p95


def read(run):
    if not run.records:
        return None
    lat = [(r.done - r.due) if r.ok else float("inf") for r in run.records]
    return 1e3 * p95(lat)
