"""Dense decoder-only transformer (qwen3 / qwen1.5 families).

Layers are *stacked* ((L, ...) leading dim) and iterated with lax.scan so the
HLO is O(1) in depth - required to keep the 61-100-layer dry-run compiles
tractable.  Per-layer remat (jax.checkpoint) bounds training activation
memory.  The same machinery (stacked init + scanned blocks) is reused by the
MoE/VLM/hybrid families.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.launch.sharding import dp_axes, shard
from repro.models import attention as attn_mod
from repro.models import layers as L
from repro.models import moe as moe_mod


def init_block(key, cfg: ModelConfig, dtype, n_stack: int):
    """One stacked residual block: ln1 -> attn -> ln2 -> mlp/moe."""
    k1, k2 = jax.random.split(key)
    p = {
        "ln1": jnp.ones((n_stack, cfg.d_model), dtype),
        "ln2": jnp.ones((n_stack, cfg.d_model), dtype),
        "attn": attn_mod.init_attention(k1, cfg, dtype, n_stack),
    }
    if cfg.family == "moe":
        p["moe"] = moe_mod.init_moe(k2, cfg, dtype, n_stack)
    else:
        p["mlp"] = L.init_mlp(k2, cfg.d_model, cfg.d_ff, dtype, n_stack)
    return p


def block_apply(
    x, p, cfg: ModelConfig, *, causal=True, cache=None, pos=None,
    prefill_cache=False, page_table=None, prefill_len=None,
):
    cd = cfg.jnp_compute_dtype()
    h, new_cache = attn_mod.attention(
        L.rms_norm(x, p["ln1"], cfg.norm_eps), p["attn"], cfg,
        causal=causal, cache=cache, pos=pos, prefill_cache=prefill_cache,
        page_table=page_table, prefill_len=prefill_len,
    )
    x = x + h.astype(x.dtype)
    ff_in = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    if cfg.family == "moe":
        ff = moe_mod.moe_ffn(ff_in, p["moe"], cfg)
    else:
        ff = L.mlp(ff_in, p["mlp"], cd)
    x = x + ff.astype(x.dtype)
    x = shard(x, dp_axes(), None, None)
    return x, new_cache


def init_lm(cfg: ModelConfig, key) -> dict:
    dt = cfg.jnp_param_dtype()
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "embed": L.init_embed(k1, cfg.vocab_size, cfg.d_model, dt),
        "blocks": init_block(k2, cfg, dt, cfg.n_layers),
        "final_norm": jnp.ones((cfg.d_model,), dt),
        "lm_head": L.dense_init(k3, cfg.d_model, cfg.vocab_size, dt),
    }


def _scan_blocks(x, stacked, cfg, *, cache=None, pos=None, prefill_cache=False,
                 causal=True, page_table=None, prefill_len=None):
    """lax.scan over stacked layer params (+ optional stacked caches).

    ``page_table`` (shared by all layers - one physical page id addresses
    the same slot of every per-layer pool) is closed over rather than
    scanned; the per-layer cache leaves carried through ``xs`` are the
    dense (B, max_len, kv_dim) slices or the paged (P, page, kv_dim) pools.
    """

    def body(carry, xs):
        if cache is None:
            lp = xs
            c = None
        else:
            lp, c = xs
        fn = functools.partial(
            block_apply, cfg=cfg, causal=causal, pos=pos,
            prefill_cache=prefill_cache, page_table=page_table,
            prefill_len=prefill_len,
        )
        if cfg.remat:
            fn = jax.checkpoint(fn)
        y, nc = fn(carry, lp, cache=c)
        return y, nc

    xs = stacked if cache is None else (stacked, cache)
    x, new_caches = jax.lax.scan(body, x, xs)
    return x, new_caches


def forward(
    params, cfg: ModelConfig, tokens: jnp.ndarray, *,
    cache=None, pos=None, prefill_cache=False,
) -> Tuple[jnp.ndarray, Optional[dict]]:
    """tokens (B, S) -> final hidden states (B, S, D) (+ updated caches)."""
    cd = cfg.jnp_compute_dtype()
    x = L.embed(tokens, params["embed"], cd)
    x, new_caches = _scan_blocks(
        x, params["blocks"], cfg, cache=cache, pos=pos,
        prefill_cache=prefill_cache,
    )
    return L.rms_norm(x, params["final_norm"], cfg.norm_eps), new_caches


def loss_fn(params, cfg: ModelConfig, batch) -> jnp.ndarray:
    tokens = batch["tokens"]
    h, _ = forward(params, cfg, tokens[:, :-1])
    return L.lm_loss_chunked(
        h, params["lm_head"], batch.get("labels", tokens[:, 1:]),
        chunk=cfg.loss_chunk,
    )


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    shape = (cfg.n_layers, batch, max_len, cfg.kv_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def init_paged_cache(
    cfg: ModelConfig, num_pages: int, page_size: int, dtype=jnp.bfloat16,
    mesh=None,
):
    """Physical page pool for all layers: (L, num_pages, page_size, kv_dim).

    Unlike the dense cache there is no batch dim - capacity is pooled
    across sequences and rationed by the engine's PageAllocator.  Keep
    ``page_size == cfg.attention.block_kv`` so page granularity coincides
    with PASA block granularity (see runtime/paged_cache.py).

    ``dtype`` may be a quantized pool dtype ("fp8_e4m3"/"int8" or the jnp
    dtypes): the pool then carries per-page, per-kv-head scale/shift
    sidecar leaves and the attention layer quantizes on write /
    dequantizes in-kernel on read.

    ``mesh`` shards every leaf over the mesh's ``model`` axis along the
    kv-head dimension (runtime/paged_cache.pool_shardings) - the
    tensor-parallel pool layout the sharded ServeEngine serves from.
    """
    from repro.runtime.paged_cache import init_paged_pool

    return init_paged_pool(
        cfg.n_layers, num_pages, page_size, cfg.kv_dim, dtype,
        n_kv_heads=cfg.n_kv_heads, mesh=mesh,
    )


def serve_step(params, cfg: ModelConfig, token: jnp.ndarray, pos: jnp.ndarray,
               cache: dict):
    """One decode step: token (B,), pos (B,) -> (logits (B, V), new cache)."""
    cd = cfg.jnp_compute_dtype()
    x = L.embed(token[:, None], params["embed"], cd)  # (B, 1, D)
    x, new_cache = _scan_blocks(x, params["blocks"], cfg, cache=cache, pos=pos)
    h = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = L.lm_logits(h[:, 0], params["lm_head"])
    logits = shard(logits, dp_axes(), "model")
    return logits, new_cache


def serve_step_paged(
    params, cfg: ModelConfig, token: jnp.ndarray, pos: jnp.ndarray,
    cache: dict, page_table: jnp.ndarray,
):
    """One decode step against the paged pool: token (B,), pos (B,),
    page_table (B, max_pages) -> (logits (B, V), updated pool).

    Numerically this is the same computation as :func:`serve_step` on a
    dense cache holding the same tokens (both decode paths use the
    masked valid-column shift; see models/attention.py), so outputs are
    bit-comparable between the two cache layouts.
    """
    cd = cfg.jnp_compute_dtype()
    x = L.embed(token[:, None], params["embed"], cd)  # (B, 1, D)
    x, new_cache = _scan_blocks(
        x, params["blocks"], cfg, cache=cache, pos=pos, page_table=page_table,
    )
    h = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = L.lm_logits(h[:, 0], params["lm_head"])
    logits = shard(logits, dp_axes(), "model")
    return logits, new_cache


def prefill(params, cfg: ModelConfig, tokens: jnp.ndarray, cache: dict):
    """Prefill a zero-initialized cache; returns (hidden, filled cache)."""
    return forward(params, cfg, tokens, cache=cache, prefill_cache=True)


def prefill_logits(params, cfg: ModelConfig, tokens: jnp.ndarray, cache: dict):
    """Fused whole-prompt prefill: (B, S) tokens -> (last-position logits
    (B, V), filled cache).  One forward pass replaces S decode steps; the
    argmax of the returned logits is the first generated token and decode
    continues at pos == S (launch/serve.py dense route)."""
    h, new_cache = forward(params, cfg, tokens, cache=cache, prefill_cache=True)
    logits = L.lm_logits(h[:, -1], params["lm_head"])
    logits = shard(logits, dp_axes(), "model")
    return logits, new_cache


def prefill_step_paged(
    params, cfg: ModelConfig, tokens: jnp.ndarray, start: jnp.ndarray,
    kv_len: jnp.ndarray, last_idx: jnp.ndarray, cache: dict,
    page_table: jnp.ndarray,
):
    """One chunked-prefill step against the paged pool.

    tokens (B, CS) - one prompt chunk PER ROW, right-padded to the static
    chunk size (pad positions write K/V to the null page).  Rows may
    belong to different requests (the engine's batched multi-request
    prefill); a fully-dead pad row carries kv_len == 0 and an all-null
    page-table row, writes only to the null sink, and its logits row is
    discarded by the caller;
    start (B,) - absolute position of the chunk's first token; with a
    QUANTIZED pool this must be page-aligned and CS a page multiple
    (quantize-on-write is page-granular; see models/attention.py);
    kv_len (B,) - valid KV length after this chunk (start + real length);
    last_idx (B,) - row of the chunk whose logits the caller wants (the
    last REAL row; only meaningful on the chunk that completes the prompt).

    Returns (logits (B, V) of the requested row, updated pool).  K/V for
    positions [start, kv_len) are written to the page table's pages; the
    attention is the chunk-exact paged prefill (models/attention.py), so
    the pages end up bit-identical to any other chunk schedule - the
    prefix-cache sharing contract.
    """
    cd = cfg.jnp_compute_dtype()
    x = L.embed(tokens, params["embed"], cd)          # (B, CS, D)
    x, new_cache = _scan_blocks(
        x, params["blocks"], cfg, cache=cache, pos=start,
        prefill_cache=True, page_table=page_table, prefill_len=kv_len,
    )
    h = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    hl = jnp.take_along_axis(
        h, last_idx.astype(jnp.int32)[:, None, None], axis=1
    )[:, 0]                                            # (B, D)
    logits = L.lm_logits(hl, params["lm_head"])
    logits = shard(logits, dp_axes(), "model")
    return logits, new_cache
