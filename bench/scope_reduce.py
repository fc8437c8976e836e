#!/usr/bin/env python3
"""Profiler trace -> device time per named scope of each program, and the
device's idle time per host span.

    python3 bench/scope_reduce.py <trace dir or .xplane.pb> [program.hlo ...]

prints the reduction as one JSON object; each ``program.hlo`` is the text
of a compiled program (``jax.jit(f).lower(...).compile().as_text()``).

The program traces each part of its model step under a ``jax.named_scope``
(the names in :data:`SCOPES`), so the ``op_name`` metadata of every op
holds its part's name as one component of a ``/``-separated path; the
engine opens a profiler annotation ``serve.<phase>`` over each phase of a
step.  The scope names are written out here, not taken from the program:
a scope the program renames reads as unscoped time.

A TPU trace names each device op by its HLO instruction and holds no
``op_name``, so the op names come from the compiled program's HLO text
(:func:`op_names`), matched by module name and instruction name.  An
instruction with no ``op_name`` of its own (a copy, an async copy or a
slice XLA inserted) takes that of the value it copies: its first operand's.

Two stages, as in ``trace_reduce.py``.  :func:`load` keeps the device
planes' ``XLA Modules`` events and ``XLA Ops`` events (each op by its
instruction name, with the event's own ``tf_op`` where a backend records
one), and the host annotations named ``bench.*`` or ``serve.*``.
:func:`reduce` computes:

  * the traced window: from the first host annotation's start to the last
    one's end;
  * ``programs``: per program (module name without its ``(id)`` suffix),
    over its runs that start in the window, ``{"s": device seconds,
    "runs": runs, "scopes": {scope: seconds}}``, where each op inside a
    run counts its self time (less the ops nested inside it) toward the
    innermost scope in its ``op_name``, or toward ``unscoped``; ``s``
    less the scopes' sum is what no op covers (gaps inside a run);
  * ``idle_by_span``: the window's device idle time (no op running),
    labelled by the innermost host annotation over it (a ``serve.*``
    annotation before a ``bench.*`` one, then the latest to start), or
    ``none``, as ``[[label, seconds], ...]``, largest first.
"""

from __future__ import annotations

import bisect
import glob
import json
import re
import sys
from pathlib import Path

SCOPES = ("embed", "qkv", "kv_write", "kv_gather", "pasa", "attn_out",
          "mlp", "head", "sample", "layer_scan")
UNSCOPED = "unscoped"
HOST_PREFIXES = ("serve.", "bench.")
MODULES, OPS = "XLA Modules", "XLA Ops"

_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.-]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def op_names(hlo_text: str):
    """``(module name, {instruction: op_name})`` of a compiled program's
    HLO text; an instruction without an ``op_name`` takes its first
    operand's (followed until one has a name, else "")."""
    module = re.search(r"^HloModule ([\w.-]+)", hlo_text, re.M)
    own, first = {}, {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, rest = m.groups()
        found = _OP_NAME.search(rest)
        if found:
            own[name] = found.group(1)
        else:
            operand = re.search(r"%([\w.-]+)", rest)
            first[name] = operand.group(1) if operand else None
    out = dict(own)
    for name in first:
        seen, cur = set(), name
        while cur is not None and cur not in own and cur not in seen:
            seen.add(cur)
            cur = first.get(cur)
        out[name] = own.get(cur, "")
    return (module.group(1) if module else ""), out


def _instruction(event_name: str) -> str:
    """``%fusion.12 = bf16[4,8]{1,0} fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%").split(" ", 1)[0]


def load(path: str) -> dict:
    """The parts of a ``.xplane.pb`` (or of the newest one under a
    directory) that :func:`reduce` reads: ``{"device": {plane:
    {"XLA Modules": [[name, start_ns, dur_ns], ...], "XLA Ops":
    [[instruction, start_ns, dur_ns, tf_op or ""], ...]}}, "host": [[name,
    start_ns, dur_ns], ...]}``."""
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
                if line.name == MODULES:
                    lines[MODULES] = [
                        [ev.name, float(ev.start_ns), float(ev.duration_ns)]
                        for ev in line.events
                    ]
                elif line.name == OPS:
                    lines[OPS] = [
                        [_instruction(ev.name), float(ev.start_ns),
                         float(ev.duration_ns), dict(ev.stats).get("tf_op", "")]
                        for ev in line.events
                    ]
            if any(lines.values()):
                device[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIXES):
                        host.append(
                            [ev.name, float(ev.start_ns), float(ev.duration_ns)]
                        )
    return {"device": device, "host": host}


def scope_of(op_path: str) -> str:
    """The innermost of :data:`SCOPES` among the components of an
    ``op_name``, else :data:`UNSCOPED`."""
    for part in reversed(op_path.split("/")):
        if part in SCOPES:
            return part
    return UNSCOPED


def _module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def _self_times(ops):
    """``[[start, dur, scope], ...]`` -> ``[[scope, self time], ...]``: each
    op's duration less that of the ops nested directly inside it."""
    out, stack = [], []
    for s, d, scope in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            out[stack[-1][0]][1] -= d
        out.append([scope, d])
        stack.append((len(out) - 1, s + d))
    return out


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _idle_by_span(gaps, host):
    """Total gap time under each innermost host annotation."""
    points = sorted({x for g in gaps for x in g}
                    | {x for _, s, d in host for x in (s, s + d)})
    starts = sorted(((s, s + d, n) for n, s, d in host), key=lambda h: h[0])
    gaps = sorted(gaps)
    totals, active, nxt, gi = {}, [], 0, 0
    for a, b in zip(points, points[1:]):
        while nxt < len(starts) and starts[nxt][0] <= a:
            active.append(starts[nxt])
            nxt += 1
        active = [h for h in active if h[1] > a]
        while gi < len(gaps) and gaps[gi][1] <= a:
            gi += 1
        if gi == len(gaps) or not gaps[gi][0] <= a < gaps[gi][1]:
            continue
        best = max(active, key=lambda h: (h[2].startswith("serve."), h[0]),
                   default=None)
        label = best[2] if best else "none"
        totals[label] = totals.get(label, 0.0) + (b - a)
    return totals


def reduce(trace: dict, names: dict | None = None) -> dict:
    """``names``: ``{module name: {instruction: op_name}}`` (:func:`op_names`
    of each program), for ops whose event holds no ``op_name``."""
    names = names or {}
    host = trace["host"]
    if not host or not trace["device"]:
        raise ValueError("trace holds no host annotation or no device")
    w0 = min(s for _, s, _ in host)
    w1 = max(s + d for _, s, d in host)
    programs, idle = {}, {}
    for lines in trace["device"].values():
        runs = sorted((s, s + d, _module_name(n))
                      for n, s, d in lines.get(MODULES, []))
        run_starts = [r[0] for r in runs]
        in_run = {}
        for instr, s, d, path in lines.get(OPS, []):
            i = bisect.bisect_right(run_starts, s) - 1
            if i >= 0 and s < runs[i][1] and w0 <= runs[i][0] < w1:
                path = path or names.get(runs[i][2], {}).get(instr, "")
                in_run.setdefault(i, []).append([s, d, scope_of(path)])
        for i, (s, e, name) in enumerate(runs):
            if not w0 <= s < w1:
                continue
            p = programs.setdefault(name, {"s": 0.0, "runs": 0, "scopes": {}})
            p["s"] += (e - s) * 1e-9
            p["runs"] += 1
            for scope, t in _self_times(in_run.get(i, [])):
                p["scopes"][scope] = p["scopes"].get(scope, 0.0) + t * 1e-9
        evs = lines.get(OPS) or lines.get(MODULES) or []
        spans = _union([max(s, w0), min(s + d, w1)] for _, s, d, *_ in evs
                       if s + d > w0 and s < w1)
        edges = [w0] + [x for sp in spans for x in sp] + [w1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        for label, t in _idle_by_span(gaps, host).items():
            idle[label] = idle.get(label, 0.0) + t * 1e-9
    return {
        "window_s": (w1 - w0) * 1e-9,
        "programs": programs,
        "idle_by_span": sorted(([k, v] for k, v in idle.items()),
                               key=lambda kv: -kv[1]),
    }


def per_run_ms(red: dict, fragment: str, scopes) -> float | None:
    """Device milliseconds per run of the programs whose name holds
    ``fragment``, spent in ``scopes`` (None: no such program ran)."""
    hits = [p for k, p in red["programs"].items() if fragment in k]
    runs = sum(p["runs"] for p in hits)
    if not runs:
        return None
    return sum(p["scopes"].get(s, 0.0) for p in hits for s in scopes) / runs * 1e3


if __name__ == "__main__":
    hlo = dict(op_names(Path(f).read_text()) for f in sys.argv[2:])
    print(json.dumps(reduce(load(sys.argv[1]), hlo), indent=1))
