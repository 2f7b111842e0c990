"""unpack_mma_roofline: the fused unpack-multiply-add aggregation kernel's
share of its roofline -- the least time of the round's launches from their
shapes (harness/kernels.py) over their summed device time in the trace.
Reported in the quant cells.  Moves tokens_per_s."""
from harness.kernels import unpack_mma, roofline_share


def counts(nb, b, up, mix):
    return unpack_mma(mix["clients"], nb, b, up["bits"])


def read(ctx):
    return roofline_share(ctx, "unpack_mma", "quant", counts)
