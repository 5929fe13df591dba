"""Engine loop: device-idle milliseconds per engine step in dispatch.

Inside the engine's dispatch spans (``serve.prefill``, ``serve.decode``,
``serve.verify``, ``serve.scrub`` and ``serve.cow``: building a
program's inputs, uploading them and launching it), the span time in
which no operation ran on the device, summed over the window and
divided by its ``engine.step`` spans, as ``engine.host_ms_per_step`` is.
"""
SPANS = ("serve.prefill", "serve.decode", "serve.verify", "serve.scrub", "serve.cow")


def read(ctx):
    tr = ctx["trace"]
    steps = tr.spans_named("engine.step")
    spans = [s for name in SPANS for s in tr.spans_named(name)]
    if not steps or not spans:
        return None
    idle = sum((e - s) * 1e-9 - b for (s, e), b in zip(spans, tr.busy_in(spans)))
    return 1e3 * idle / len(steps)
