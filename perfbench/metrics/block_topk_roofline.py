"""block_topk_roofline: the block-wise top-k selection kernel's share
of its roofline -- the least time of the round's launches from their
shapes (harness/kernels.py) over their summed device time in the trace.
Reported in the topk cells.  Moves tokens_per_s."""
from harness.kernels import block_topk, roofline_share, topk_k


def counts(nb, b, up, mix):
    return block_topk(nb, b, topk_k(b, up["ratio"]), mix["participating"])


def read(ctx):
    return roofline_share(ctx, "block_topk", "topk", counts)
