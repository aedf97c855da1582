"""Share of its roofline that ``mla_sparse_decode_attention`` reaches: the
least time the chip could take for the kernel's algorithmic work in the
traced window (bench/kernels/mla_sparse_decode_attention.py), over the
kernel's device time."""


def read(ctx):
    return ctx.roofline("mla_sparse_decode_attention")
