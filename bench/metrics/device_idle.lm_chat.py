"""Share of the traced window in which no operation ran on the device."""
from harness import trace


def read(run):
    if run.flat is None:
        return None
    busy = trace.busy_ns(run.flat)
    w = trace.window(run.flat)
    if busy is None or w is None or w[1] <= w[0]:
        return None
    return 100.0 * (1.0 - busy / (w[1] - w[0]))
