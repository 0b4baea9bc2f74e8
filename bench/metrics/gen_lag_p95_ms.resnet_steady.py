"""How late the open-loop sender ran: 95th percentile of (sent - due) over
the requests due in the window."""
from harness.stats import p95


def read(run):
    lags = [r.sent - r.due for r in run.records if r.sent]
    return 1e3 * p95(lags) if lags else None
