"""Paged decode-attention kernel: the bytes its real lengths need at
the HBM peak over the kernel's device time.

Per decoded token: the K and V blocks that hold the keys it attends,
and its query and output, in every layer (``arith.decode_attention_bytes``),
split over the cell's chips, against the kernel's time per chip.
"""
import arith

KERNELS = ("paged_decode_attention",)


def read(ctx):
    kernel_s = ctx["trace"].kernel_s(KERNELS)
    if kernel_s <= 0:
        return None
    conf = ctx["config"]
    model, block = conf["model"], conf["engine"]["block_size"]
    nbytes = sum(arith.decode_attention_bytes(model, keys, block)
                 for s in ctx["window"].steps for keys in s.decode)
    return arith.roofline_share(nbytes, kernel_s, ctx["chips"],
                                ctx["peaks"]["hbm_bytes_per_s"])
