"""The engine's per-step ``engine`` counter samples (``StepTracer``,
trace runs only): the work each step dispatched, counted by the program
where it enqueues the call."""


def share(run, useful, attempted):
    """Sum of the ``useful`` counter over the sum of the ``attempted`` one,
    across the samples of the steps that ended inside the window, in
    percent; None without telemetry, when the program counts no such
    work, or when nothing was attempted."""
    if not run.tel_events:
        return None
    lo, hi = run.t0 - run.tel_offset, run.t1 - run.tel_offset
    samples = [e.args for e in run.tel_events
               if e.kind == "counter" and e.name == "engine" and lo <= e.ts < hi]
    if not samples or useful not in samples[0] or attempted not in samples[0]:
        return None
    total = sum(s[attempted] for s in samples)
    return sum(s[useful] for s in samples) / total * 100.0 if total else None
