"""95th percentile of a request's wait in the dispatcher's admission queue,
``aeg.wait``: from its submit to the scheduler until the dispatcher pops
it, over the requests whose header arrived in the window. Read
from the program's span ring; a program without one reads nothing."""
from harness import program_spans
from harness.stats import p95


def read(run):
    reqs = program_spans.of_run(run)
    ns = [v for v in (r.dur("aeg.wait") for r in reqs or [])
          if v is not None]
    return p95(ns) / 1e6 if ns else None
