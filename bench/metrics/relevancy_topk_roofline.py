"""Share of its roofline that ``relevancy_topk`` reaches: the least time
the chip could take for the kernel's algorithmic work in the traced window
(bench/kernels/relevancy_topk.py), over the kernel's device time."""


def read(ctx):
    return ctx.roofline("relevancy_topk")
