"""idle_share: 100 * (1 - union of device-op intervals / traced window).
Moves tokens_per_s."""


def read(ctx):
    if not ctx.trace.devices:
        return None
    return 100.0 * ctx.trace.idle_share
