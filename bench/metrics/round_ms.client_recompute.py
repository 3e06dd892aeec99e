"""Device ms a round of the client's rematerialized forward: ops under
the ``eris.client`` scope inside a ``rematted_computation``, averaged over
the chips (``bench/scopes.py``)."""
from bench import scopes


def read(ctx):
    return scopes.round_ms(ctx, "client_recompute")
