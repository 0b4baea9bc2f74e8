"""95th percentile of a request's residence in the server: from its INFER
header's arrival (start of ``aeg.recv``) to the end of its reply's send
(end of ``aeg.reply``), over the requests whose header arrived in the window. The client's latency adds the network, the client's own
threads and the time before the header arrives. Read from the program's
span ring; a program without one reads nothing."""
from harness import program_spans
from harness.stats import p95


def read(run):
    reqs = program_spans.of_run(run)
    return p95([program_spans.residence_ns(r) for r in reqs]) / 1e6 \
        if reqs else None
