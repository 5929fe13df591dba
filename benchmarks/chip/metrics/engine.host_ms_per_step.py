"""Engine loop: host milliseconds per engine step.

Mean over the window's engine steps of the harness's ``engine.step``
span less the device-busy time inside it: scheduling, input transfer,
sampling and bookkeeping that the device does not overlap.
"""
SPAN = "engine.step"


def read(ctx):
    tr = ctx["trace"]
    spans = tr.spans_named(SPAN)
    if not spans:
        return None
    busy = tr.busy_in(spans)
    host = [(e - s) * 1e-9 - b for (s, e), b in zip(spans, busy)]
    return 1e3 * sum(host) / len(host)
