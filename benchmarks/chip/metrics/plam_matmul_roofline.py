"""PLAM kernel: least time of the matmuls' own work over the kernel's
device time.

Per call the least time is the larger of 2*M*K*N at the bf16 peak and
its posit16 weight and input bytes plus float32 output bytes at the HBM
peak (``arith.matmul_least_s``).  A decode step multiplies ``max_slots``
rows through every layer and the output head; a prompt chunk its padded
width through the layers and one row through the head.  The least time
is split over the cell's chips, against the kernel's time per chip.
"""
import arith

KERNELS = ("plam_matmul",)


def read(ctx):
    kernel_s = ctx["trace"].kernel_s(KERNELS)
    if kernel_s <= 0:
        return None
    conf, pk, sites = ctx["config"], ctx["peaks"], ctx["sites"]
    model, block = conf["model"], conf["engine"]["block_size"]
    slots = conf["engine"]["max_slots"]
    least = 0.0
    for s in ctx["window"].steps:
        if s.chunk:
            width = -(-s.chunk[1] // block) * block
            least += arith.program_matmul_least_s(sites, model, width, 1, pk)
        if s.decode:
            least += arith.program_matmul_least_s(sites, model, slots, slots, pk)
    return arith.roofline_share(least, kernel_s, ctx["chips"])
