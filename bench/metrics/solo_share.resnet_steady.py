"""Share of the requests the dispatcher took in the window that it served
alone on the linked path, not coalesced into a batched dispatch."""
from harness.stats import delta


def read(run):
    done, batched = delta(run, "processed"), delta(run, "batched_requests")
    if not done or batched is None:
        return None
    return 100.0 * (1.0 - batched / done)
