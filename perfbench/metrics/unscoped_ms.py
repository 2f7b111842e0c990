"""unscoped_ms: device milliseconds per round of ops under no scope of
the program after metadata-less ops take their enclosing loop's, the
benchmark's batch generator left out; ``scopes.unscoped_ops`` names them
(harness/scopes.py).  Moves tokens_per_s."""
from harness import scopes


def read(ctx):
    return scopes.ms(ctx, lambda p: p.name == "unscoped")
