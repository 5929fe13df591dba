"""Model step: model FLOPs of the window's work over the window's
seconds, as a share of the bf16 peak of the cell's chips.

Per prompt token and per decoded token: 2 FLOPs per matmul MAC of the
layers, plus attention (QK^T and PV) over the keys it attends, plus
the output head where the token's logits are used (decoded tokens and
the last prompt token).
"""
import arith


def window_flops(sites, model, steps):
    total = 0
    for s in steps:
        if s.chunk:
            start, real, plen = s.chunk
            for p in range(start, start + real):
                total += arith.token_flops(sites, model, p + 1, logits=(p == plen - 1))
        for keys in s.decode:
            total += arith.token_flops(sites, model, keys, logits=True)
    return total


def read(ctx):
    win = ctx["window"]
    flops = window_flops(ctx["sites"], ctx["config"]["model"], win.steps)
    return arith.peak_share(flops, win.seconds, ctx["peaks"]["bf16_flops_per_s"],
                            ctx["chips"])
