"""Share of the prefill calls' static grid that holds real prompt tokens:
the engine's ``prefill_tokens`` over its ``prefill_slots`` (prefill_batch
x prefill_chunk per call), summed over the window's steps, in percent."""

from _engine_work import share


def read(run):
    return share(run, "prefill_tokens", "prefill_slots")
