"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

The traced run marks its window on the host with the span
``bench.window`` and each round's host work with ``bench.next_batch``,
``bench.dispatch`` and ``bench.read_loss``.  From the device planes'
``XLA Ops`` lines, clipped to that window, it takes:

- busy time: the union of the intervals in which an op runs, averaged
  over the devices;
- per-op time and calls, by HLO instruction name, summed over the
  devices and divided by their number (a per-device average); ops that
  only hold others (a loop, a branch) are left out, their inner ops
  count;
- exposed collective time: the part of the union of collective ops that
  no other op on that device overlaps;
- idle gaps: the stretches of the window with no op, each named by the
  innermost harness span on the host that covers its middle.
"""
from __future__ import annotations

import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"^(all-gather|all-to-all|reduce-scatter|all-reduce|collective-permute|"
    r"send|recv)")
HOST_SPANS = ("bench.next_batch", "bench.dispatch", "bench.read_loss")
# ops that only hold other ops of the same line (a loop, a branch, a call)
CONTAINERS = {"while", "conditional", "call"}
_KIND = re.compile(r"\s([a-z][a-z0-9\-]*)\(")


def op_name(event_name: str) -> tuple:
    """(instruction name, op kind) of an ``XLA Ops`` event, which is named
    by its whole HLO instruction: ``%fusion.3 = bf16[..] fusion(...)``."""
    if " = " not in event_name:
        return event_name, event_name
    name, rest = event_name.split(" = ", 1)
    kind = _KIND.search(" " + rest)
    return name.strip().lstrip("%"), kind.group(1) if kind else ""


def load(directory: Path):
    """The ProfileData of the one trace under ``directory``."""
    from jax.profiler import ProfileData
    files = sorted(Path(directory).rglob("*.xplane.pb"))
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {directory}: {files}")
    return ProfileData.from_file(str(files[0]))


def _union(iv: list) -> list:
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(iv: list) -> float:
    return sum(e - s for s, e in iv)


def _subtract(a: list, b: list) -> list:
    """a minus b, both sorted disjoint interval lists."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def _events(line):
    for ev in line.events:
        yield ev.name, ev.start_ns, ev.start_ns + ev.duration_ns


def _device_ops(line):
    """(instruction name, kind, start, end) of the line's leaf ops."""
    for ev, s, e in _events(line):
        name, kind = op_name(ev)
        if kind not in CONTAINERS:
            yield name, kind, s, e


def reduce(profile, n_devices: int) -> dict:
    """The trace's window, busy and idle time, per-op time and the longest
    idle gaps; times in seconds."""
    host, devices = [], []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.append(list(_device_ops(line)))
        else:
            for line in plane.lines:
                host += [e for e in _events(line) if e[0].startswith("bench.")]
    windows = [(s, e) for n, s, e in host if n == "bench.window"]
    if len(windows) != 1 or not devices:
        raise RuntimeError(f"trace holds {len(windows)} windows and "
                           f"{len(devices)} device op lines")
    w0, w1 = windows[0]
    spans = [(n, s, e) for n, s, e in host if n in HOST_SPANS]
    busy, exposed, collective, per_op, calls, gaps = 0.0, 0.0, 0.0, {}, {}, []
    for ops in devices[:n_devices]:
        ops = [(n, k, max(s, w0), min(e, w1))
               for n, k, s, e in ops if e > w0 and s < w1]
        for n, _, s, e in ops:
            per_op[n] = per_op.get(n, 0.0) + (e - s)
            calls[n] = calls.get(n, 0) + 1
        on = _union([[s, e] for _, _, s, e in ops])
        busy += _length(on)
        coll = _union([[s, e] for _, k, s, e in ops if COLLECTIVE.match(k)])
        comp = _union([[s, e] for _, k, s, e in ops if not COLLECTIVE.match(k)])
        exposed += _length(_subtract(coll, comp))
        collective += _length(coll)
        for s, e in _subtract([[w0, w1]], on):
            mid = (s + e) / 2
            cover = [(se - ss, n) for n, ss, se in spans if ss <= mid <= se]
            gaps.append((min(cover)[1] if cover else "other", (e - s) * 1e-9))
    n = min(n_devices, len(devices))
    per_op = {k: v * 1e-9 / n for k, v in per_op.items()}
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    gaps.sort(key=lambda g: -g[1])
    return {"window_s": (w1 - w0) * 1e-9, "busy_s": busy * 1e-9 / n,
            "exposed_collective_s": exposed * 1e-9 / n,
            "collective_s": collective * 1e-9 / n,
            "op_seconds": per_op,
            "op_calls": {k: v / n for k, v in calls.items()},
            "top_ops": [[k, v] for k, v in top],
            "idle_gaps": [[k, v] for k, v in gaps[:10]]}
