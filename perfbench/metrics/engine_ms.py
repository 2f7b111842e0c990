"""engine_ms: device milliseconds per round of the round engine -- ops
under ``round.sample_round`` and ``round.server_update``, and the round
program's ops under no ``round.*`` scope (flatten, unflatten, bookkeeping).
The benchmark's own batch generator is not counted.  Moves tokens_per_s."""

STAGES = ("round.sample_round", "round.server_update", "")


def read(ctx):
    if not ctx.trace.devices:
        return None
    st = ctx.trace.stage_s()
    return 1e3 * sum(st.get(s, 0.0) for s in STAGES) / ctx.trace.rounds
