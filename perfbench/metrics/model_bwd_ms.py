"""model_bwd_ms: device milliseconds per round of the model's backward
passes -- ops under a ``model.*`` scope inside JAX's ``transpose(`` name
stack, the rematerialized forward included (harness/scopes.py).  Moves
tokens_per_s."""
from harness import scopes


def read(ctx):
    return scopes.ms(ctx, lambda p: p.name == "model" and p.backward)
