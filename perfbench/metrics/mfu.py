"""mfu: the whole round step's share of the chip's bf16 peak -- model
FLOPs of the forward and backward passes of the trained tokens (the
configuration's ``flops_per_token``, recomputation not counted) times the
traced window's trained tokens per second, over the peak.  Moves
tokens_per_s."""


def read(ctx):
    if not ctx.peaks:
        return None
    per_token = ctx.ref_mod.flops_per_token(ctx.cell.config["model"],
                                            ctx.cell.traffic["seq"])
    return 100.0 * per_token * ctx.tokens_per_s / ctx.peaks["bf16_flops"]
