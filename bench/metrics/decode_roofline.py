"""Decode program against the chip's roofline: the least time the work of
the traced decode calls needs (``bench/work.py``: weights once per call,
K/V of each live row at its real length; operations over the bf16 peak or
bytes over HBM bandwidth, whichever is larger), over the device time the
calls took, per call, in percent."""

from _serving import DECODE, program_time, traced_steps
from work import decode_call, roofline_s


def read(run):
    dev = program_time(run, DECODE)
    if dev is None or run.peaks is None:
        return None
    traced = {id(s) for s in traced_steps(run)}
    ks = {k for k, s in enumerate(run.steps, start=1) if id(s) in traced}
    calls = {}
    # a decode call's tokens reach the client when the NEXT step retires it
    for _, kv_len, k in run.decode_tokens:
        if k - 1 in ks:
            calls.setdefault(k, []).append(kv_len)
    if not calls:
        return None
    least = sum(roofline_s(*decode_call(run.conf, kv), run.peaks)
                for kv in calls.values()) / len(calls)
    return least / (dev[0] / dev[1]) * 100.0
