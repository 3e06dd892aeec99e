"""Device ms a round of the client-axis collectives: ops under the
``eris.exchange`` scope and the FSA parameter all-gather at the
``shard_map`` boundary, averaged over the chips (``bench/scopes.py``)."""
from bench import scopes


def read(ctx):
    return scopes.round_ms(ctx, "exchange")
