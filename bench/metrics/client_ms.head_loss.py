"""Device ms a round of the final norm, the head matmul and the
cross-entropy: ops under the ``model.head_loss`` scope, forward and
backward, averaged over the chips (``bench/scopes.py``)."""
from bench import scopes


def read(ctx):
    return scopes.client_ms(ctx, "head_loss")
