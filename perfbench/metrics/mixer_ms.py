"""mixer_ms: device milliseconds per round under ``model.mixer``, forward
and backward: the attention blocks in smollm, the SSD mixer in mamba2,
each with its pre-norm (harness/scopes.py).  Moves tokens_per_s."""
from harness import scopes


def read(ctx):
    return scopes.ms(ctx, lambda p: p.name == "model" and p.kind == "mixer")
