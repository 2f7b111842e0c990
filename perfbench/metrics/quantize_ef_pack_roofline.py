"""quantize_ef_pack_roofline: the fused EF14 quantize-and-pack kernel's share
of its roofline -- the least time of the round's launches from their
shapes (harness/kernels.py) over their summed device time in the trace.
Reported in the quant cells.  Moves tokens_per_s."""
from harness.kernels import quantize_ef_pack, roofline_share


def counts(nb, b, up, mix):
    return quantize_ef_pack(nb, b, up["bits"], mix["participating"])


def read(ctx):
    return roofline_share(ctx, "quantize_ef_pack", "quant", counts)
