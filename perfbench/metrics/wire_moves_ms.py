"""wire_moves_ms: device milliseconds per round of the flat wire's data
movement outside its Pallas kernels -- ops under ``comm.rows`` (row takes
and puts) and ``comm.relayout`` (the ``[n, span] <-> [n, nb, block]``
views, pads and concatenations), outside the local steps and the
diagnostic norm (harness/scopes.py).  Moves tokens_per_s."""
from harness import scopes


def read(ctx):
    return scopes.ms(ctx, lambda p: p.name == "wire_moves")
