"""Reduction of a ``jax.profiler`` trace to the benchmark's device numbers.

The traced window is the host span named ``WINDOW`` that the harness
opens around the part of the run it traces. Within it:

* busy: the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:`` plane), averaged
  over the devices that ran anything; idle share = 1 - busy / window;
* the device operations and the XLA modules that took most time;
* the longest idle gaps, each named by the host event that overlaps it
  most (the shortest such event on a tie): what the host was doing
  while the device waited.

``reduce_planes`` takes plain data (planes of lines of events), so a
test can build a trace by hand; ``reduce_file`` reads an
``.xplane.pb`` through ``jax.profiler.ProfileData``.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, Tuple

WINDOW = "bench_window"
TOP = 10
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Event = Tuple[str, float, float]            # name, start_ns, end_ns
# plane name -> its lines, each (line name, events); line names repeat
# (every Python thread's line is named "python")
Planes = Dict[str, List[Tuple[str, List[Event]]]]


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(ev: List[Event], w0: float, w1: float) -> List[Event]:
    return [(n, max(a, w0), min(b, w1)) for n, a, b in ev
            if b > w0 and a < w1]


def op_name(name: str) -> str:
    """A device op's event carries its whole HLO instruction; keep the
    instruction's name (``%fusion.182 = bf16[...] fusion(...)`` ->
    ``fusion.182``)."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def _top(ev: List[Event]) -> List[list]:
    tot: Dict[str, float] = defaultdict(float)
    for n, a, b in ev:
        tot[op_name(n)] += b - a
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:TOP]
    return [[n, t * 1e-9] for n, t in ranked]


def _window(planes: Planes) -> Tuple[float, float]:
    for pname, lines in planes.items():
        if pname.startswith("/device:"):
            continue
        for _, ev in lines:
            for n, a, b in ev:
                if n == WINDOW:
                    return a, b
    raise ValueError(f"no host span named {WINDOW!r} in the trace")


def _line(lines, name: str) -> List[Event]:
    return [e for n, ev in lines if n == name for e in ev]


def _name_gap(a: float, b: float, host: List[Event]) -> str:
    best, key = "host: no traced event", None
    for n, s, e in host:
        ov = min(b, e) - max(a, s)
        if ov <= 0 or n == WINDOW:
            continue
        k = (ov, -(e - s))
        if key is None or k > key:
            best, key = n, k
    return best


def reduce_planes(planes: Planes) -> dict:
    w0, w1 = _window(planes)
    host = [e for pname, lines in planes.items()
            if not pname.startswith("/device:")
            for _, ev in lines for e in ev]
    host = _clip(host, w0, w1)
    busy, ops, mods, gaps = [], [], [], []
    for pname, lines in planes.items():
        if not pname.startswith("/device:") or "CPU" in pname:
            continue
        dev_ops = _clip(_line(lines, OPS_LINE), w0, w1)
        if not dev_ops:
            continue
        ops += dev_ops
        mods += _clip(_line(lines, MODULES_LINE), w0, w1)
        u = union([(a, b) for _, a, b in dev_ops])
        busy.append(sum(b - a for a, b in u))
        edges = [w0] + [x for iv in u for x in iv] + [w1]
        for i in range(0, len(edges), 2):
            a, b = edges[i], edges[i + 1]
            if b > a:
                gaps.append((a, b))
    if not busy:
        return {"window_s": (w1 - w0) * 1e-9, "busy_s": None, "devices": 0}
    gaps.sort(key=lambda g: g[0] - g[1])
    named = [[_name_gap(a, b, host), (b - a) * 1e-9] for a, b in gaps[:TOP]]
    return {"window_s": (w1 - w0) * 1e-9,
            "busy_s": sum(busy) / len(busy) * 1e-9,
            "devices": len(busy),
            "device_ops": _top(ops), "modules": _top(mods),
            "idle_gaps": named}


def planes_of(profile) -> Planes:
    """Plain data from a ``jax.profiler.ProfileData``."""
    out: Planes = {}
    for p in profile.planes:
        out[p.name] = [(ln.name, [(e.name, e.start_ns, e.end_ns)
                                  for e in ln.events]) for ln in p.lines]
    return out


def reduce_file(path: str) -> dict:
    from jax.profiler import ProfileData
    return reduce_planes(planes_of(ProfileData.from_file(path)))
