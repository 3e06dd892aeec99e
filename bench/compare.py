"""The comparison that decides a training cell's ``correct``.

Numbers read of the program's first rounds against the plain reference's
(``reference/eris.py``) from the same seed:

- ``loss_gap``: the largest |loss - reference loss| / reference loss over
  the compared rounds, and ``first_loss_gap`` that of the first round,
  which no random draw of the round touches;
- ``update_gap``: the worst leaf's gap between the norm of the first
  aggregated update the optimizer got and the reference's, and
  ``update_gap_median`` the median leaf's;
- ``change_gap``: the worst leaf's gap between the norm of the weights'
  change over the compared rounds and the reference's;
- ``shift_gap`` (with DSC): the median over the leaves' client and
  aggregator shift states after the compared rounds of the gap between
  their norms and the reference's.

A leaf's gap is |program norm - reference norm| over the larger of the
reference's norm of that leaf and of the median leaf.  Leaves whose first
gradient in the reference is below a thousandth of the median leaf's are
left out of both: their gradient is nought to rounding (a key bias under
softmax), and Adam moves them by round-off alone.  The limits of a cell,
the numbers it compares and its control's precision are in
``bench/limits/<workload>.json`` with the readings they were set from.
"""
from __future__ import annotations

import math
import statistics

ZERO_GRADIENT = 1e-3


def compared_leaves(ref) -> list:
    median = statistics.median(ref.grad.values())
    return [k for k, g in ref.grad.items() if g >= ZERO_GRADIENT * median]


def _gaps(got: dict, want: dict, leaves: list) -> list:
    median = statistics.median(want[k] for k in leaves)
    return [abs(got[k] - want[k]) / max(want[k], median, 1e-30)
            for k in leaves]


def numbers(prog, ref) -> dict:
    """{number: value} of the program's readings against the reference's
    (both :class:`bench.reference.eris.Readings`)."""
    leaves = compared_leaves(ref)
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog.losses, ref.losses))
    if not all(math.isfinite(x) for x in prog.losses):
        loss = math.inf
    update = _gaps(prog.update, ref.update, leaves)
    change = _gaps(prog.change, ref.change, leaves)
    first = abs(prog.losses[0] - ref.losses[0]) / abs(ref.losses[0])
    out = {"loss_gap": loss, "first_loss_gap": first,
           "update_gap": max(update), "update_gap_median": statistics.median(update),
           "change_gap": max(change)}
    if ref.shift is not None:
        shift = [f"{k}/{kind}" for k in leaves for kind in ("clients", "agg")]
        out["shift_gap"] = statistics.median(
            _gaps(prog.shift, ref.shift, shift))
    return out


def decide(values: dict, limits: dict) -> tuple[bool, list]:
    """(correct, [[name, value, limit], ...]) over the numbers that
    ``limits`` names: correct when each is finite and at or under its
    limit."""
    checks = [[k, values[k], lim] for k, lim in limits["limits"].items()]
    ok = all(math.isfinite(v) and v <= lim for _, v, lim in checks)
    return ok, checks
