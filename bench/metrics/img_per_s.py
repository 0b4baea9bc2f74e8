"""Images in the replies received in the window, per second of window."""
from harness.stats import done_in


def read(run):
    return sum(r.units for r in done_in(run.all_records, *run.window)) \
        / run.seconds
