"""Share of the K/V positions the decode calls gathered that their rows
attend: the engine's ``kv_live`` (cursor + 1 per decoding row) over its
``kv_slots`` (max_batch x max_pages_per_seq x page_size per call), summed
over the window's steps, in percent."""

from _engine_work import share


def read(run):
    return share(run, "kv_live", "kv_slots")
