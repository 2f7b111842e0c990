"""wire_ms: device milliseconds per round of the flat wire -- ops under
``round.encode_reduce`` (EF encode, row moves, aggregation) and
``round.downlink``.  Moves tokens_per_s."""

STAGES = ("round.encode_reduce", "round.downlink")


def read(ctx):
    if not ctx.trace.devices:
        return None
    st = ctx.trace.stage_s()
    return 1e3 * sum(st.get(s, 0.0) for s in STAGES) / ctx.trace.rounds
