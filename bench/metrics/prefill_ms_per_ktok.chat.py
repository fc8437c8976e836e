"""Device time of the prefill program per 1000 real prompt tokens it
processed (padding is cost, not work), in chat cells."""

from _serving import prefill_ms_per_ktok


def read(run):
    return prefill_ms_per_ktok(run)
