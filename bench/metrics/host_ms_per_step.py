"""Host time of the engine's plan and dispatch spans (``StepTracer``), per
engine step inside the window, in milliseconds.  The ``retire`` span is
left out: it is mostly the wait for the device's tokens."""


def read(run):
    if not run.tel_events:
        return None
    lo, hi = run.t0 - run.tel_offset, run.t1 - run.tel_offset
    spans = [e for e in run.tel_events
             if e.kind == "span" and e.name in ("plan", "dispatch") and lo <= e.ts < hi]
    steps = {e.step for e in spans if e.name == "plan"}
    if not steps:
        return None
    return sum(e.dur for e in spans) / len(steps) * 1e3
