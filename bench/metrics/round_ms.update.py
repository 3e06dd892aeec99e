"""Device ms a round of the server update on the aggregator's segment
(optimizer, parameter add, gradient norm): ops under the ``eris.update``
scope, averaged over the chips (``bench/scopes.py``)."""
from bench import scopes


def read(ctx):
    return scopes.round_ms(ctx, "update")
