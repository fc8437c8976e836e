"""Generated tokens that reached clients inside the window, per second."""


def read(run):
    n = sum(1 for r in run.recs for t in r.times if run.t0 <= t < run.t1)
    return n / run.seconds
