"""model_ms: device milliseconds per round of the model -- ops under
``round.eval_round`` and ``round.local_deltas`` (the constraint query's
forward and the E local steps).  Moves tokens_per_s."""

STAGES = ("round.eval_round", "round.local_deltas")


def read(ctx):
    if not ctx.trace.devices:
        return None
    st = ctx.trace.stage_s()
    return 1e3 * sum(st.get(s, 0.0) for s in STAGES) / ctx.trace.rounds
