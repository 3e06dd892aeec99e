"""Plain references.  A configuration's file names its model's reference
(``"reference": "<module>"``), a module of this package with ``dims``,
``layout``, ``init`` and ``loss``; ``eris.py`` is the round around it."""
import importlib


def model(conf: dict):
    """The reference module that ``conf`` names."""
    return importlib.import_module(f"{__name__}.{conf['reference']}")
