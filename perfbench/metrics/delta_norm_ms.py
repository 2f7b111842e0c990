"""delta_norm_ms: device milliseconds per round under
``engine.delta_norm``: the per-round diagnostic norm of the aggregated
deltas, its scatter of the participants' rows (``segment_rows``) included
(harness/scopes.py).  Moves tokens_per_s."""
from harness import scopes


def read(ctx):
    return scopes.ms(ctx, lambda p: p.name == "delta_norm")
