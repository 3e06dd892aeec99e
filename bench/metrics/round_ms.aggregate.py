"""Device ms a round of the aggregator's receive side (dequantize,
reduce, the Eq. 4 shift compensation): ops under the ``eris.aggregate``
scope, averaged over the chips (``bench/scopes.py``)."""
from bench import scopes


def read(ctx):
    return scopes.round_ms(ctx, "aggregate")
