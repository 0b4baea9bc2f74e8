"""Share of the first device's idle time in the traced window that lies
inside at least one request's stay in the server (start of ``aeg.recv`` to
end of ``aeg.reply``): the device idles while the host holds a request.
The program's spans are put on the profiler's clock by the offset
``program_spans.fit`` finds from the harness's send spans; no number
without a trace, a ring or a fit."""
from harness import program_spans


def read(run):
    spans = program_spans.ring()
    if run.flat is None or spans is None:
        return None
    return program_spans.held_idle(run.flat, run.all_records,
                                   program_spans.group(spans))
