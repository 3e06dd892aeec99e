"""Device ms a round of the client's forward pass: ops under the
``eris.client`` scope that are neither recomputed nor backward, averaged
over the chips (``bench/scopes.py``)."""
from bench import scopes


def read(ctx):
    return scopes.round_ms(ctx, "client_fwd")
