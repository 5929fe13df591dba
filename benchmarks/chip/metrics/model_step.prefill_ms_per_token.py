"""Model step: device milliseconds of prefill-chunk programs per real
prompt token written in the window."""
PROGRAM = "jit_paged_prefill_chunk"


def read(ctx):
    calls = ctx["trace"].program_calls(name_prefix=PROGRAM)
    tokens = sum(s.chunk[1] for s in ctx["window"].steps if s.chunk)
    if not calls or not tokens:
        return None
    return 1e3 * sum(calls) / tokens
