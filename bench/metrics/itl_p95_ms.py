"""95th percentile of the gaps between consecutive tokens of a request, over
every gap that closes inside the window (host clock, milliseconds)."""

from _serving import nearest_rank


def read(run):
    gaps = [
        b - a for r in run.recs for a, b in zip(r.times, r.times[1:])
        if run.t0 <= b < run.t1
    ]
    v = nearest_rank(gaps, 95)
    return None if v is None else v * 1e3
