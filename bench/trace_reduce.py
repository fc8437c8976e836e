"""Profiler trace -> device busy and idle time, time per program, idle gaps.

Two stages.  :func:`load` reads the ``.xplane.pb`` that ``jax.profiler``
wrote and keeps what the reduction needs, as plain lists (the form of the
recorded trace the tests check against): the device planes' ``XLA Modules``
(one event per program run) and ``XLA Ops`` lines, and the host events
whose names start with the benchmark's annotation prefix.  :func:`reduce`
computes:

  * the traced window: from the start of the first benchmark annotation
    to the end of the last (the benchmark annotates every step, submit and
    wait of its loop, so the annotations tile the window);
  * ``busy_s``: the union of the intervals in which an operation ran on a
    device, inside the window, averaged over the devices that ran any;
  * ``programs``: per program (module name without its ``(id)`` suffix),
    device seconds and runs, counting the runs that start in the window;
  * ``device_ops``: the ten operations that took most device time inside
    the window, each counted by its self time (less the ops nested inside
    it);
  * ``idle_gaps``: the ten longest gaps between busy intervals, each named
    by the benchmark annotation that overlaps it most (``none`` if none).
"""

from __future__ import annotations

import glob
import re

PREFIX = "bench."
MODULES, OPS = "XLA Modules", "XLA Ops"


def load(path: str) -> dict:
    """The parts of a ``.xplane.pb`` (or the directory holding one) that
    :func:`reduce` reads: ``{"device": {plane: {line: [[name, start_ns,
    dur_ns], ...]}}, "host": [[name, start_ns, dur_ns], ...]}``."""
    from jax.profiler import ProfileData

    if not path.endswith(".pb"):
        found = sorted(glob.glob(f"{path}/**/*.xplane.pb", recursive=True))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    data = ProfileData.from_file(path)
    device, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {}
            for line in plane.lines:
                if line.name in (MODULES, OPS):
                    lines[line.name] = [
                        [ev.name, float(ev.start_ns), float(ev.duration_ns)]
                        for ev in line.events
                    ]
            if any(lines.values()):
                device[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PREFIX):
                        host.append(
                            [ev.name, float(ev.start_ns), float(ev.duration_ns)]
                        )
    return {"device": device, "host": host}


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def _op_name(name: str) -> str:
    """``%fusion.12 = bf16[4,8]{1,0} fusion(...)`` -> ``fusion.12 fusion
    bf16[4,8]``: the instruction, its opcode and its result's shape."""
    head, _, rest = name.partition(" = ")
    if not rest:
        return name
    op = re.search(r" ([a-z][a-z0-9-]*)\(", rest)
    shape = re.match(r"(\w+\[[\d,]*\])", rest)
    parts = [head.lstrip("%"), op.group(1) if op else ""]
    return " ".join(p for p in parts + [shape.group(1) if shape else ""] if p)


def _self_times(evs):
    """Each op's duration less that of the ops nested directly inside it
    (the ops line holds a while loop and the ops of its body alike)."""
    out, stack = [], []
    for name, s, d in sorted(evs, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            out[stack[-1][0]][1] -= d
        out.append([name, d])
        stack.append((len(out) - 1, s + d))
    return out


def _top(totals: dict, n: int = 10):
    return [[k, v] for k, v in sorted(totals.items(), key=lambda kv: -kv[1])[:n]]


def reduce(trace: dict) -> dict:
    host = trace["host"]
    if not host or not trace["device"]:
        raise ValueError("trace holds no benchmark annotation or no device")
    w0 = min(s for _, s, _ in host)
    w1 = max(s + d for _, s, d in host)
    busy, programs, ops, gaps = [], {}, {}, []
    for lines in trace["device"].values():
        evs = lines.get(OPS) or lines.get(MODULES) or []
        spans = _union(
            [max(s, w0), min(s + d, w1)] for _, s, d in evs
            if s + d > w0 and s < w1
        )
        if not spans:
            continue
        busy.append(sum(e - s for s, e in spans))
        for name, s, d in lines.get(MODULES, []):
            if w0 <= s < w1:
                t, c = programs.get(_module_name(name), (0.0, 0))
                programs[_module_name(name)] = (t + d * 1e-9, c + 1)
        inside = [[n, max(s, w0), min(s + d, w1) - max(s, w0)]
                  for n, s, d in lines.get(OPS, []) if s + d > w0 and s < w1]
        for name, d in _self_times(inside):
            ops[_op_name(name)] = ops.get(_op_name(name), 0.0) + d * 1e-9
        edges = [w0] + [x for sp in spans for x in sp] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    if not busy:
        raise ValueError("no device operation ran inside the traced window")

    def label(gap):
        s, e = gap
        best, name = 0.0, "none"
        for n, hs, hd in host:
            ov = min(e, hs + hd) - max(s, hs)
            if ov > best:
                best, name = ov, n
        return name

    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(busy) / len(busy) * 1e-9,
        "programs": {k: [t, c] for k, (t, c) in programs.items()},
        "device_ops": _top(ops),
        "idle_gaps": [[label(g), (g[1] - g[0]) * 1e-9] for g in gaps[:10]],
    }


def program(red: dict, fragment: str):
    """(seconds, runs) summed over the programs whose name holds
    ``fragment``; None when no such program ran in the window."""
    hits = [v for k, v in red["programs"].items() if fragment in k]
    if not hits:
        return None
    return sum(t for t, _ in hits), sum(c for _, c in hits)
