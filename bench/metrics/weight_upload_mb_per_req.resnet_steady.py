"""Weight bytes copied from host to device per request served, in MB (1e6
bytes): the ``weight_h2d_bytes`` of every ``aeg.issue`` span, counting
each dispatch once, over the requests dispatched whose header arrived in
the window. A solo request of a host-view bind copies the whole weight
image; a bind pinned on the device copies none. Read from the program's
span ring; nothing where no issue span carries the count."""
from harness import program_spans


def read(run):
    reqs = [r for r in program_spans.of_run(run) or []
            if r.dispatch is not None]
    dispatches = {r.dispatch.id: r.children for r in reqs}
    counts = [c.stats["weight_h2d_bytes"]
              for kids in dispatches.values() for c in kids
              if c.name == "aeg.issue" and c.stats
              and "weight_h2d_bytes" in c.stats]
    return sum(counts) / len(reqs) / 1e6 if counts else None
