"""90th percentile of time to first token over the requests due in the
window: from when a request was due to when its first token reached the
client; a request with no first token counts as missing (+inf)."""

from _serving import finite, nearest_rank


def read(run):
    due = run.due_in_window()
    return finite(nearest_rank(
        [r.first - r.due if r.first is not None else float("inf") for r in due], 90
    ))
