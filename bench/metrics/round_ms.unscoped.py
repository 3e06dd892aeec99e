"""Device ms a round of ops under no ``eris.`` scope, other than the
parameter all-gather, averaged over the chips (``bench/scopes.py``): what
the phase split does not cover.  A scope renamed in the program shows
here."""
from bench import scopes


def read(ctx):
    return scopes.round_ms(ctx, "unscoped")
