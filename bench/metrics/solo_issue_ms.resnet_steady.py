"""Mean host time to issue a solo request's linked program, ``aeg.issue``
under a solo ``aeg.dispatch``: the dispatcher calling one jitted handler
per RCB op through RHAL, without waiting for the device. Over the solo
dispatches of requests whose header arrived in the window;
read from the program's span ring, nothing without one."""
import statistics

from harness import program_spans


def read(run):
    ns = program_spans.solo_issue_ns(program_spans.of_run(run) or [])
    return statistics.fmean(ns) / 1e6 if ns else None
