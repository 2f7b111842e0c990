"""head_loss_ms: device milliseconds per round under ``model.embed``,
``model.head`` and ``model.loss``, forward and backward: the token
embedding, the final norm and tied logits, and the cross-entropies f and g
(harness/scopes.py).  Moves tokens_per_s."""
from harness import scopes


def read(ctx):
    return scopes.ms(ctx, lambda p: p.name == "model"
                     and p.kind in ("embed", "head", "loss"))
