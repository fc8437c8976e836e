"""Operations and bytes that the algorithm needs, counted from shapes.

Counts follow the model, not its implementation: a matmul of (m, k) by
(k, n) is 2mkn operations; attention of one query at context ``c`` is
2c operations per head dimension for QK^T and as many for PV; norms,
rotary embedding and softmax are left out (under 1% of a layer).  Bytes
are what one call must move through HBM at least: every weight it applies
once, the embedding rows it looks up, and the K/V of each live row at its
actual length, in the pool's dtype.  Padding, idle batch rows and the
gathered copies an implementation makes count for nothing.

``conf`` is a configuration file of ``bench/configs`` (Hugging Face keys).
"""

from __future__ import annotations


def dims(conf: dict) -> dict:
    h = conf["num_attention_heads"]
    hd = conf.get("head_dim") or conf["hidden_size"] // h
    return dict(
        d=conf["hidden_size"], ff=conf["intermediate_size"],
        layers=conf["num_hidden_layers"], heads=h,
        kv_heads=conf["num_key_value_heads"], head_dim=hd,
        vocab=conf["vocab_size"],
    )


def layer_matmul_params(conf: dict) -> int:
    """Weights of one layer's projections and MLP (what a token multiplies)."""
    m = dims(conf)
    q, kv = m["heads"] * m["head_dim"], m["kv_heads"] * m["head_dim"]
    return m["d"] * q + 2 * m["d"] * kv + q * m["d"] + 3 * m["d"] * m["ff"]


def layer_params(conf: dict) -> int:
    """Every weight of one layer: projections, MLP, norms, biases."""
    m = dims(conf)
    q, kv = m["heads"] * m["head_dim"], m["kv_heads"] * m["head_dim"]
    n = layer_matmul_params(conf) + 2 * m["d"]
    if conf.get("qkv_bias"):
        n += q + 2 * kv
    if conf.get("qk_norm"):
        n += 2 * m["head_dim"]
    return n


def token_matmul_flops(conf: dict) -> int:
    """Matmul operations of one token through every layer (no LM head)."""
    return 2 * dims(conf)["layers"] * layer_matmul_params(conf)


def head_flops(conf: dict) -> int:
    m = dims(conf)
    return 2 * m["d"] * m["vocab"]


def attention_flops(conf: dict, context: int) -> int:
    """QK^T and PV of one query over ``context`` positions, all layers."""
    m = dims(conf)
    return 4 * m["layers"] * m["heads"] * m["head_dim"] * int(context)


def prefill_chunk_flops(conf: dict, start: int, n: int, completes: bool) -> int:
    """Positions ``start .. start + n - 1`` of one prompt, causal; the LM
    head once, on the chunk that completes the prompt."""
    contexts = n * start + n * (n + 1) // 2       # sum of (p + 1)
    out = n * token_matmul_flops(conf) + attention_flops(conf, 1) * contexts
    return out + (head_flops(conf) if completes else 0)


def decode_token_flops(conf: dict, kv_len: int) -> int:
    """One generated token whose step attends over ``kv_len`` positions."""
    return token_matmul_flops(conf) + attention_flops(conf, kv_len) + head_flops(conf)


def weight_bytes(conf: dict, dtype_bytes: int = 2) -> int:
    """Weights a decode call applies once: layers, final norm and LM head
    (the embedding table is read a row per token, counted apart)."""
    m = dims(conf)
    n = m["layers"] * layer_params(conf) + m["d"] + m["d"] * m["vocab"]
    return n * dtype_bytes


def kv_bytes_per_position(conf: dict, dtype_bytes: int = 2) -> int:
    m = dims(conf)
    return 2 * m["layers"] * m["kv_heads"] * m["head_dim"] * dtype_bytes


def decode_call(conf: dict, kv_lens, dtype_bytes: int = 2,
                kv_dtype_bytes: int = 2):
    """(operations, bytes) of one batched decode call whose live rows
    attend over ``kv_lens`` positions each (the new token's K/V included:
    written once, and read as part of its own context)."""
    kv_lens = [int(c) for c in kv_lens]
    flops = sum(decode_token_flops(conf, c) for c in kv_lens)
    per_pos = kv_bytes_per_position(conf, kv_dtype_bytes)
    nbytes = (
        weight_bytes(conf, dtype_bytes)
        + len(kv_lens) * dims(conf)["d"] * dtype_bytes     # embedding rows
        + sum(kv_lens) * per_pos
    )
    return flops, nbytes


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: compute or memory bound."""
    return max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
