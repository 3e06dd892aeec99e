"""Device ms a round of the model's attention (projections, rope, flash
attention, output projection): ops under the ``model.attention`` scope,
forward, recomputation and backward, averaged over the chips
(``bench/scopes.py``)."""
from bench import scopes


def read(ctx):
    return scopes.client_ms(ctx, "attention")
