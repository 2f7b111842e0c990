"""scatter_agg_roofline: the bucketed top-k aggregation kernel's share
of its roofline -- the least time of the round's launches from their
shapes (harness/kernels.py) over their summed device time in the trace.
Reported in the topk cells.  Moves tokens_per_s."""
from harness.kernels import scatter_agg, roofline_share, topk_k


def counts(nb, b, up, mix):
    return scatter_agg(mix["clients"], nb, b, topk_k(b, up["ratio"]))


def read(ctx):
    return roofline_share(ctx, "scatter_agg", "topk", counts)
