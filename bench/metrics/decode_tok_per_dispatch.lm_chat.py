"""Tokens each live lane advances per decode dispatch in the traced
window: the decode window the engine chose, weighted by its live lanes
(the ladder is 8/4/2/1; lower means more host round trips per token)."""


def read(run):
    if run.trace_span is None:
        return None
    lo, hi = run.trace_span
    ev = [e for e in run.log if e[0] == "decode" and lo <= e[1] < hi and e[2]]
    lanes = sum(e[2] for e in ev)
    return sum(e[2] * e[3] for e in ev) / lanes if lanes else None
