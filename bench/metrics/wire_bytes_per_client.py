"""Bytes one device sends over the client axes in one round, from the
compiled round's collectives, ring-weighted (``bench/hlo.py``)."""
from bench import hlo


def read(ctx):
    total = sum(hlo.collective_bytes(ctx["hlo"]).values())
    return total or None
