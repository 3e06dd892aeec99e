"""Device time of the traced rounds split by phase of the ERIS round and
by part of the model.

The program names each phase of its round with a ``jax.named_scope``
(``eris.client``, ``eris.compress``, ``eris.exchange``,
``eris.aggregate``, ``eris.update``) and each part of the model
(``model.embed``, ``model.attention``, ``model.mlp``,
``model.head_loss``).  The compiled round's text (``ctx["hlo"]``) carries
the scopes in each instruction's ``metadata={op_name="jit(step)/..."}``,
and the trace keys device time by the same instruction names
(``ctx["trace"]["op_seconds"]``).  Each op goes to exactly one phase:

- under ``eris.client``: recomputed (``rematted_computation`` in its
  op_name), else backward (``transpose(``), else forward;
- else by its innermost ``eris.`` scope: compress, exchange, aggregate or
  update;
- an ``all-gather`` under no ``eris.`` scope is the FSA parameter
  broadcast at the ``shard_map`` boundary: exchange;
- anything else: unscoped.

An op the compiler made, whose op_name names no op of the program (none,
a parameter's name, or the ``jit``/``shard_map`` boundary alone), is
looked up by what it works for: a fusion by its root, then by the
nearest op under a scope that reads its result (a layout copy before a
matmul, the parameter copies before the optimizer), then by the nearest
one it reads (a copy of the new state into the round's outputs).

The scope strings are written here again, not imported from the
program: a scope renamed in the program shows as unscoped time rising,
instead of being followed.
"""
from __future__ import annotations

import functools
import re

from bench import trace

PHASES = ("client_fwd", "client_recompute", "client_bwd", "compress",
          "exchange", "aggregate", "update", "unscoped")
PARTS = ("embed", "attention", "mlp", "head_loss")
# a scope is one level of the op_name's path, which a transformation may
# wrap: ".../eris.client/transpose(jvp(model.head_loss))/dot_general"
_ERIS = re.compile(r"(?:^|[/(])eris\.(client|compress|exchange|aggregate|"
                   r"update)(?=[/)]|$)")
_MODEL = re.compile(r"(?:^|[/(])model\.(embed|attention|mlp|head_loss)"
                    r"(?=[/)]|$)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_REF = re.compile(r"%([\w.\-]+)")
# what follows an instruction's operands
_ATTRS = re.compile(r",\s*(?:calls|to_apply|condition|body|"
                    r"branch_computations|metadata|backend_config)=")
# an op_name that names no op of the program
_COMPILER_MADE = re.compile(r"[^/]*|jit\([^/]*\)/shard_map")


def phase(op_name: str, kind: str) -> str:
    """The phase of one instruction, from its op_name and its opcode."""
    scopes = _ERIS.findall(op_name)
    if not scopes:
        return "exchange" if kind.startswith("all-gather") else "unscoped"
    if scopes[-1] != "client":
        return scopes[-1]
    if "rematted_computation" in op_name:
        return "client_recompute"
    return "client_bwd" if "transpose(" in op_name else "client_fwd"


def part(op_name: str):
    """The innermost ``model.`` part of one instruction, or None."""
    parts = _MODEL.findall(op_name)
    return parts[-1] if parts else None


@functools.lru_cache(maxsize=4)
def instructions(hlo_text: str) -> dict:
    """{instruction name: (opcode, op_name)} of every instruction of the
    program, an op the compiler made looked up by what it works for (the
    module's docstring)."""
    out, calls, roots, operands, comp = {}, {}, {}, {}, None
    for line in hlo_text.splitlines():
        s = line.strip()
        if " = " not in s:
            m = _COMPUTATION.match(s)
            if m:
                comp = m.group(1)
            continue
        is_root = s.startswith("ROOT ")
        name, kind = trace.op_name(s.removeprefix("ROOT "))
        m = _OP_NAME.search(s)
        out[name] = (kind, m.group(1) if m else "")
        operands[name] = [r for r in _REF.findall(
            _ATTRS.split(s.split(" = ", 1)[1])[0]) if r != name]
        c = None if m else _CALLS.search(s)
        if c:
            calls[name] = c.group(1)
        if is_root and comp is not None:
            roots[comp] = name
    for name, callee in calls.items():
        root = roots.get(callee)
        if root is not None:
            out[name] = (out[name][0], out[root][1])
    users = {}
    for name, refs in operands.items():
        for r in refs:
            users.setdefault(r, []).append(name)
    scoped = [n for n, (_, o) in out.items() if _ERIS.search(o)]
    by_user, by_operand = _nearest(scoped, operands), _nearest(scoped, users)
    for name, (kind, op_name) in list(out.items()):
        if (_COMPILER_MADE.fullmatch(op_name)
                and phase(op_name, kind) == "unscoped"):
            source = by_user.get(name) or by_operand.get(name)
            if source is not None:
                out[name] = (kind, out[source][1])
    return out


def _nearest(sources: list, edges: dict) -> dict:
    """{instruction: the nearest of ``sources`` it is reached from along
    ``edges``}, breadth first from all sources at once."""
    near = {s: s for s in sources}
    frontier = list(sources)
    while frontier:
        nxt = []
        for n in frontier:
            for m in edges.get(n, ()):
                if m not in near:
                    near[m] = near[n]
                    nxt.append(m)
        frontier = nxt
    return near


def split(ctx) -> dict:
    """{"phases": {phase: (device seconds, ops)}, "parts": {part: (device
    seconds, ops)}} of the traced rounds, per device; every op of
    ``op_seconds`` in exactly one phase."""
    instr = instructions(ctx["hlo"])
    phases = {p: [0.0, 0] for p in PHASES}
    parts = {p: [0.0, 0] for p in PARTS}
    for op, sec in ctx["trace"]["op_seconds"].items():
        kind, op_name = instr.get(op, ("", ""))
        ph = phases[phase(op_name, kind)]
        ph[0] += sec
        ph[1] += 1
        pt = part(op_name)
        if pt is not None:
            parts[pt][0] += sec
            parts[pt][1] += 1
    return {"phases": {k: tuple(v) for k, v in phases.items()},
            "parts": {k: tuple(v) for k, v in parts.items()}}


def _per_round(ctx, seconds_ops):
    sec, ops = seconds_ops
    return 1000.0 * sec / ctx["rounds"] if ops else None


def round_ms(ctx, name: str):
    """Device ms a round of phase ``name``, averaged over the chips; None
    where no op of the traced rounds falls in it."""
    return _per_round(ctx, split(ctx)["phases"][name])


def client_ms(ctx, name: str):
    """Device ms a round of model part ``name``, forward, recomputation
    and backward together, averaged over the chips; None where no op of
    the traced rounds carries it."""
    return _per_round(ctx, split(ctx)["parts"][name])
