"""compile_s: backend compile seconds during set-up, from JAX's monitoring
events (a persistent-cache hit reports its retrieval time).  Moves
setup_s."""


def read(ctx):
    return ctx.compile_s
