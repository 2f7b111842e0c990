"""mlp_ms: device milliseconds per round under ``model.mlp``, forward and
backward: the SwiGLU blocks with their pre-norm (harness/scopes.py).
Reported in the smollm cells.  Moves tokens_per_s."""
from harness import scopes


def read(ctx):
    return scopes.ms(ctx, lambda p: p.name == "model" and p.kind == "mlp")
