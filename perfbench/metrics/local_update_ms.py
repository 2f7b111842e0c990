"""local_update_ms: device milliseconds per round of the local steps
outside the model -- ops of ``round.eval_round`` / ``round.local_deltas``
(metadata-less ops by their enclosing loop) under no ``model.*`` scope:
``local.update``, the gradient's assembly into ``[d]``, cotangents, the
layer scans' slicing and stacking (harness/scopes.py).  Moves
tokens_per_s."""
from harness import scopes


def read(ctx):
    return scopes.ms(ctx, lambda p: p.name == "local_update")
