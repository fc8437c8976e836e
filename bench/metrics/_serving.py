"""What several readers share: tails of client-side times, and device
time per program from the reduced profiler trace."""

import math

from harness import percentile as nearest_rank  # noqa: F401
from trace_reduce import program

#: the engine's jitted programs, by the names of the functions they trace
DECODE, PREFILL = "_device_step", "_device_prefill"


def finite(x):
    return x if x is not None and math.isfinite(x) else None


def program_time(run, fragment):
    """(device seconds, runs) of the engine program, in the traced window."""
    if run.trace_red is None:
        return None
    hit = program(run.trace_red, fragment)
    return hit if hit and hit[1] else None


def traced_steps(run):
    """Host step records whose step began while the profiler traced."""
    if run.trace_host is None:
        return []
    t0, t1 = run.trace_host
    return [s for s in run.steps if t0 <= s[0] < t1]


def prefill_ms_per_ktok(run):
    dev = program_time(run, PREFILL)
    calls = [s for s in traced_steps(run) if s[2]]
    if dev is None or not calls:
        return None
    tokens = sum(end - start for s in calls for _, start, end in s[2])
    return (dev[0] / dev[1] * 1e3) / (tokens / len(calls) / 1e3)


def device_idle(run):
    red = run.trace_red
    if red is None or red["window_s"] <= 0:
        return None
    return (1.0 - red["busy_s"] / red["window_s"]) * 100.0
