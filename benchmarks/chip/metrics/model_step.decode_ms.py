"""Model step: device milliseconds per call of the decode-step program.

The decode-step program is the one that holds the paged decode
attention kernel.
"""
KERNEL = "paged_decode_attention_kernel"


def read(ctx):
    calls = ctx["trace"].program_calls(contains_kernel=KERNEL)
    return 1e3 * sum(calls) / len(calls) if calls else None
