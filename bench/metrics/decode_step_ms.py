"""Device time of one run of the engine's decode program (profiler trace)."""

from _serving import DECODE, program_time


def read(run):
    dev = program_time(run, DECODE)
    return None if dev is None else dev[0] / dev[1] * 1e3
