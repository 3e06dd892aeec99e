"""Device ms a round of the model's MLP: ops under the ``model.mlp``
scope, forward, recomputation and backward, averaged over the chips
(``bench/scopes.py``)."""
from bench import scopes


def read(ctx):
    return scopes.client_ms(ctx, "mlp")
