"""90th percentile of queue wait: from when a request due in the window was
due to the engine's ``admit`` telemetry instant (trace runs only)."""

from _serving import finite, nearest_rank


def read(run):
    if not run.tel_events:
        return None
    due = run.due_in_window()
    return finite(nearest_rank(
        [r.admit - r.due if r.admit is not None else float("inf") for r in due], 90
    ))
