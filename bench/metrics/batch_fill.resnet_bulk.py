"""Requests per coalesced dispatch in the window, as a share of the
server's batch window."""
from harness.stats import delta


def read(run):
    reqs, disp = delta(run, "batched_requests"), \
        delta(run, "batched_dispatches")
    if not disp:
        return None
    return 100.0 * reqs / disp / run.cfg["engine"]["batch_window"]
