"""Model FLOPs of the prompt and generated tokens the engine processed in
the window (matmuls and attention at the real context, ``bench/work.py``)
over the window times the chip's bf16 peak, in percent."""

from work import decode_token_flops, prefill_chunk_flops


def read(run):
    if run.peaks is None:
        return None
    flops = 0
    for t0, _, chunks in run.steps:
        if run.t0 <= t0 < run.t1:
            for plen, start, end in chunks:
                flops += prefill_chunk_flops(run.conf, start, end - start, end >= plen)
    flops += sum(decode_token_flops(run.conf, kv)
                 for t, kv, _ in run.decode_tokens if run.t0 <= t < run.t1)
    return flops / (run.seconds * run.peaks["bf16_flops_per_s"]) * 100.0
