"""95th percentile of a request's ingress in the server: from its INFER
header's arrival to its admission ticket, ``aeg.recv`` (body and CRC) plus
``aeg.unpack`` (npz decode and submit to the scheduler), over the requests
whose header arrived in the window. Read from the program's
span ring; a program without one reads nothing."""
from harness import program_spans
from harness.stats import p95


def read(run):
    reqs = program_spans.of_run(run)
    ns = [v for v in map(program_spans.ingress_ns, reqs or [])
          if v is not None]
    return p95(ns) / 1e6 if ns else None
