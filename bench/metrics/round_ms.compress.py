"""Device ms a round of the client's preparation of its update for the
wire (DSC, quantization, the shift update): ops under the
``eris.compress`` scope, averaged over the chips (``bench/scopes.py``)."""
from bench import scopes


def read(ctx):
    return scopes.round_ms(ctx, "compress")
