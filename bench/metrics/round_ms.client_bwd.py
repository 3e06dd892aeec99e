"""Device ms a round of the client's backward pass: ops under the
``eris.client`` scope inside a ``transpose(``, not recomputed, averaged
over the chips (``bench/scopes.py``)."""
from bench import scopes


def read(ctx):
    return scopes.round_ms(ctx, "client_bwd")
