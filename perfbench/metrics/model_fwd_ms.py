"""model_fwd_ms: device milliseconds per round of the model's forward
passes -- ops under a ``model.*`` scope and not under JAX's ``transpose(``
name stack: the constraint query's forward and the local steps' forward
(harness/scopes.py).  Moves tokens_per_s."""
from harness import scopes


def read(ctx):
    return scopes.ms(ctx, lambda p: p.name == "model" and not p.backward)
