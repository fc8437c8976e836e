"""GQA/MHA attention module with PASA as a first-class implementation switch.

Supports: qk-norm (qwen3), QKV bias (qwen1.5), RoPE, cross-attention
(S1 != S2; llama-vision / whisper), KV-cached decode, and three attention
implementations:

  * "pasa"  - the paper's algorithm at its fully-fp16 allocation (default
              paper-faithful path; bf16 inputs are converted to fp16 inside,
              as the paper prescribes),
  * "flash" - blocked FA2 at the configured (safe) precision policy,
  * "naive" - materialized softmax (tiny smoke tests only).

Head-parallel sharding: activations are constrained on the KV-head axis over
"model" (uneven shardings are legal on intermediates; see DESIGN.md).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import blocked_attention, naive_attention
from repro.core.precision import get_policy
from repro.launch.sharding import dp_axes, shard
from repro.models.layers import (
    apply_rope,
    dense_init,
    rms_norm,
    rope_tables,
    row_parallel_matmul as L_row_parallel,
)


def init_attention(key, cfg: ModelConfig, dtype, n_stack=None, kv_in_dim=None):
    keys = jax.random.split(key, 5)
    d = cfg.d_model
    kv_in = kv_in_dim or d
    p = {
        "wq": dense_init(keys[0], d, cfg.q_dim, dtype, n_stack),
        "wk": dense_init(keys[1], kv_in, cfg.kv_dim, dtype, n_stack),
        "wv": dense_init(keys[2], kv_in, cfg.kv_dim, dtype, n_stack),
        "wo": dense_init(keys[3], cfg.q_dim, d, dtype, n_stack),
    }
    if cfg.qkv_bias:
        shape = lambda n: (n,) if n_stack is None else (n_stack, n)
        p["bq"] = jnp.zeros(shape(cfg.q_dim), dtype)
        p["bk"] = jnp.zeros(shape(cfg.kv_dim), dtype)
        p["bv"] = jnp.zeros(shape(cfg.kv_dim), dtype)
    if cfg.qk_norm:
        shape = lambda n: (n,) if n_stack is None else (n_stack, n)
        p["q_norm"] = jnp.ones(shape(cfg.head_dim), dtype)
        p["k_norm"] = jnp.ones(shape(cfg.head_dim), dtype)
    return p


def _attend(q5, k5, v5, cfg: ModelConfig, *, causal, kv_len, q_offset,
            decode=False, chunk_block: int = 0):
    """q5: (B, KVH, G, S1, hd); k5/v5: (B, KVH, 1, S2, hd).

    ``decode=True`` selects the decode-kernel shift convention for PASA:
    algebraic per-block key shift and row pseudo-average over the *valid*
    (pos < kv_len) columns only (``shift_mask_valid``).  This keeps the XLA
    decode path bit-comparable to kernels/pasa_decode.py and
    pasa_paged_decode.py, and - because stale columns beyond kv_len can
    never leak into the output - is what allows recycled KV pages to skip
    scrubbing.  Both conventions are exact softmax; see
    core.pasa.blocked_attention.

    ``chunk_block > 0`` selects the chunked-prefill convention
    (``chunk_exact``: valid-column shift under causal masking with per-row
    dead-block no-ops) at block granularity ``chunk_block`` (== the KV page
    size, so shift blocks coincide with cache pages and prefix-cache hits
    are bit-identical to cold prefill; see kernels/pasa_paged_prefill.py).
    """
    ac = cfg.attention
    if ac.impl == "naive":
        # Chunked prefill puts S1 rows at a dynamic position offset; the
        # reshaped q_offset broadcasts as (..., S1, 1) against the column
        # ids once given a trailing axis (blocked_attention adds the same
        # axis internally).  Without it, a chunk at c0 > 0 would causally
        # mask out the whole cached prefix beyond column S1-1.
        qo = 0
        if chunk_block > 0 and q_offset is not None:
            qo = q_offset[..., None]
        out = naive_attention(
            q5, k5, v5, causal=causal, kv_len=kv_len,
            q_offset=qo,
        ).astype(q5.dtype)
        return out
    policy = get_policy(ac.pasa_policy if ac.impl == "pasa" else ac.policy)
    beta = ac.beta if ac.impl == "pasa" else 0.0
    if chunk_block > 0:
        return blocked_attention(
            q5, k5, v5,
            beta=beta, policy=policy, block_kv=chunk_block, causal=True,
            kv_len=kv_len, q_offset=q_offset,
            use_gemm_shift=False, chunk_exact=True,
        )
    use_gemm = ac.use_gemm_shift and not decode
    return blocked_attention(
        q5, k5, v5,
        beta=beta, policy=policy, block_kv=ac.block_kv, causal=causal,
        kv_len=kv_len, q_offset=q_offset,
        use_gemm_shift=use_gemm,
        shift_mask_valid=decode,
    )


def _gather_kv(pool, page_table):
    """This layer's K and V pages, in each row's logical order: the XLA
    gather of the raw pool, or of the codes and sidecars of a quantized
    one, dequantized to f32."""
    from repro.runtime.paged_cache import gather_pages, gather_pages_dequant

    if "k_scale" in pool:
        return tuple(
            gather_pages_dequant(
                pool[n], pool[n + "_scale"], pool[n + "_shift"], page_table
            )
            for n in ("k", "v")
        )
    return tuple(gather_pages(pool[n], page_table) for n in ("k", "v"))


def _paged_decode_backend() -> str:
    """The paged decode route this backend takes: "kernel" on a TPU, "xla"
    elsewhere.  The one seam tests monkeypatch: "interpret" runs the
    kernel in the Pallas interpreter on the CPU, and "kernel" lets a CPU
    host compile the TPU program for a described chip."""
    return "kernel" if jax.default_backend() == "tpu" else "xla"


def paged_decode_route(cfg: ModelConfig, page_size: int) -> str:
    """How a paged decode step reads this layer's pool.

    "xla": gather every row's whole page table (``_gather_kv``) and run
    ``blocked_attention`` over it.  "kernel" / "interpret": the paged
    decode kernel (kernels/pasa_paged_decode.py) reads each row's live
    pages in place.  The kernel computes the same attention, at the same
    fp16 storage points, where one page is one PASA block of the
    algebraic shift; anything else takes the XLA route on every backend.
    """
    ac = cfg.attention
    if ac.impl != "pasa" or page_size != ac.block_kv:
        return "xla"
    return _paged_decode_backend()


def paged_decode_reads(cfg: ModelConfig, page_size: int, pos,
                       max_pages: int) -> int:
    """K/V positions one paged decode call reads from a layer's pool, for
    write positions ``pos`` (0 in slots not decoding).  The XLA route
    gathers every row's whole page table, however few of its positions
    are live; the kernel's index map stops at each row's last live page,
    ``kv_len = pos + 1``, so an idle slot reads one null page."""
    pos = np.asarray(pos, np.int64)
    if paged_decode_route(cfg, page_size) == "xla":
        return pos.size * max_pages * page_size
    return int((-(-(pos + 1) // page_size)).sum()) * page_size


def _paged_decode(q, pool, page_table, kv_len, cfg: ModelConfig, *,
                  interpret: bool):
    """The paged decode kernel over this layer's pool: q (B, 1, H, hd)
    -> (B, KVH, G, hd) at the PASA policy's output dtype."""
    from repro.kernels.ops import pasa_paged_decode

    b, _, _, hd = q.shape
    kvh = cfg.n_kv_heads
    n_pages, page, _ = pool["k"].shape
    quant = {}
    if "k_scale" in pool:
        quant = dict(
            k_scale=pool["k_scale"], v_scale=pool["v_scale"],
            k_shift=pool["k_shift"].reshape(n_pages, kvh, hd),
            v_shift=pool["v_shift"].reshape(n_pages, kvh, hd),
        )
    ac = cfg.attention
    return pasa_paged_decode(
        q.reshape(b, kvh, cfg.group, hd),
        pool["k"].reshape(n_pages, page, kvh, hd),
        pool["v"].reshape(n_pages, page, kvh, hd),
        page_table, kv_len, beta=ac.beta, policy=get_policy(ac.pasa_policy),
        interpret=interpret, **quant,
    )


def attention(
    x: jnp.ndarray,                 # (B, S, D)
    p,                              # params (single layer slice)
    cfg: ModelConfig,
    *,
    causal: bool = True,
    use_rope: bool = True,
    cross_x: Optional[jnp.ndarray] = None,   # (B, S_kv, D_src) for cross-attn
    cache: Optional[dict] = None,   # {"k","v": (B, S2max, KV_dim)} dense, or
                                    # {"k","v": (P, page, KV_dim)} paged pool
    pos: Optional[jnp.ndarray] = None,       # (B,) write positions (decode)
                                             # or chunk starts (paged prefill)
    prefill_cache: bool = False,
    page_table: Optional[jnp.ndarray] = None,  # (B, max_pages) -> paged cache
    prefill_len: Optional[jnp.ndarray] = None,  # (B,) valid KV length after
                                                # this chunk (paged prefill)
) -> Tuple[jnp.ndarray, Optional[dict]]:
    cd = cfg.jnp_compute_dtype()
    b, s, _ = x.shape
    h, kvh, hd, g = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.group
    with jax.named_scope("qkv"):
        x = x.astype(cd)

        q = x @ p["wq"].astype(cd)
        src = x if cross_x is None else cross_x.astype(cd)
        s_kv = src.shape[1]
        k = src @ p["wk"].astype(cd)
        v = src @ p["wv"].astype(cd)
        if cfg.qkv_bias:
            q = q + p["bq"].astype(cd)
            k = k + p["bk"].astype(cd)
            v = v + p["bv"].astype(cd)
        q = shard(q, dp_axes(), None, "model")
        k = shard(k, dp_axes(), None, "model")

        q = q.reshape(b, s, h, hd)
        k = k.reshape(b, s_kv, kvh, hd)
        v = v.reshape(b, s_kv, kvh, hd)

        if cfg.qk_norm:
            q = rms_norm(q, p["q_norm"], cfg.norm_eps)
            k = rms_norm(k, p["k_norm"], cfg.norm_eps)

        q_offset = None
        kv_len = None
        if use_rope and cross_x is None:
            if pos is not None:
                # decode (S == 1) or chunked prefill: rotate by per-batch
                # absolute positions pos + [0, S)
                half = hd // 2
                freqs = 1.0 / (
                    cfg.rope_theta
                    ** (jnp.arange(0, half, dtype=jnp.float32) / half)
                )
                abs_pos = (
                    pos.astype(jnp.float32)[:, None]
                    + jnp.arange(s, dtype=jnp.float32)[None, :]
                )                                      # (B, S)
                ang = abs_pos[:, :, None] * freqs
                cos = jnp.cos(ang)[:, :, None, :]      # (B, S, 1, half)
                sin = jnp.sin(ang)[:, :, None, :]
                q = apply_rope(q, cos, sin)
                k = apply_rope(k, cos, sin)
            else:
                cos, sin = rope_tables(s, hd, cfg.rope_theta)
                q = apply_rope(q, cos, sin)
                k = apply_rope(k, cos, sin)

    new_cache = None
    out = None
    chunk_block = 0
    quantized = cache is not None and "k_scale" in cache
    if cache is not None and page_table is not None and prefill_cache:
        # Chunked paged prefill: scatter this chunk's K/V into its pages,
        # then attend causally over the page table - cached prefix pages
        # and the in-flight chunk read uniformly (write-then-attend).  The
        # attention runs at the chunk-exact convention with shift-block
        # granularity == page size, so every full page's contents are a
        # function of the token prefix alone and prefix-cache hits are
        # bit-identical to cold prefill (see kernels/pasa_paged_prefill.py).
        # The B rows may belong to DIFFERENT requests (the engine's batched
        # multi-request prefill): each row carries its own chunk start,
        # valid limit, and page-table row, so the per-row scatters and the
        # per-row gather+attend below are independent; dead pad rows
        # (prefill_len == 0) write only to the null sink and emit zeros.
        if pos is None or prefill_len is None:
            raise ValueError(
                "paged prefill needs pos (chunk start) and prefill_len"
            )
        from repro.runtime.paged_cache import NULL_PAGE, quantize_kv_page

        with jax.named_scope("kv_write"):
            ck, cv = cache["k"], cache["v"]
            page = ck.shape[1]
            mp = page_table.shape[1]
            positions = (
                pos.astype(jnp.int32)[:, None]
                + jnp.arange(s, dtype=jnp.int32)[None, :]
            )                                             # (B, S)
            limit = prefill_len.astype(jnp.int32)
            valid = positions < limit[:, None]
            if quantized:
                # Quantize-on-write at PAGE granularity: chunk starts are
                # page-aligned and the chunk length is a page multiple
                # (enforced by the engine), so every page of the chunk has all
                # of its valid rows in hand and its scale/shift can be
                # computed from exactly those rows - making the codes and
                # sidecar a pure function of the token prefix (the quantized
                # extension of the chunk-exact bit-invariance contract).
                if s % page:
                    raise ValueError(
                        f"quantized pool needs page-multiple chunks "
                        f"({s} % {page})"
                    )
                # pos (the chunk start) must ALSO be page-aligned; it is a
                # traced value so it cannot be checked here.  The engine
                # guarantees it (prefill_chunk is a page multiple and starts
                # advance from a page-aligned cached_len); direct callers of
                # prefill_step_paged with a misaligned start would scatter
                # whole-page codes into the wrong physical pages.
                n_cp = s // page
                validp = valid.reshape(b, n_cp, page)
                qmode = cfg.attention.kv_quant_scale
                kcodes, ksc, ksh = quantize_kv_page(
                    k.astype(jnp.float32).reshape(b, n_cp, page, kvh, hd),
                    validp, ck.dtype, scale_mode=qmode,
                )
                vcodes, vsc, vsh = quantize_kv_page(
                    v.astype(jnp.float32).reshape(b, n_cp, page, kvh, hd),
                    validp, cv.dtype, scale_mode=qmode,
                )
                page_idx = (
                    pos.astype(jnp.int32)[:, None] // page
                    + jnp.arange(n_cp, dtype=jnp.int32)[None, :]
                )                                         # (B, n_cp)
                phys_p = jnp.take_along_axis(
                    page_table, jnp.minimum(page_idx, mp - 1), axis=1
                )
                # all-pad pages (beyond the real chunk) land in the write sink
                phys_p = jnp.where(validp.any(-1), phys_p, NULL_PAGE)
                ck = ck.at[phys_p].set(kcodes.reshape(b, n_cp, page, kvh * hd))
                cv = cv.at[phys_p].set(vcodes.reshape(b, n_cp, page, kvh * hd))
                k_scale = cache["k_scale"].at[phys_p].set(ksc)
                k_shift = cache["k_shift"].at[phys_p].set(
                    ksh.reshape(b, n_cp, kvh * hd)
                )
                v_scale = cache["v_scale"].at[phys_p].set(vsc)
                v_shift = cache["v_shift"].at[phys_p].set(
                    vsh.reshape(b, n_cp, kvh * hd)
                )
                new_cache = {
                    "k": ck, "v": cv, "k_scale": k_scale, "k_shift": k_shift,
                    "v_scale": v_scale, "v_shift": v_shift,
                }
            else:
                pidx = jnp.minimum(positions // page, mp - 1)
                slot = positions % page
                phys = jnp.take_along_axis(page_table, pidx, axis=1)
                # pad rows (beyond the real chunk) land in the null write sink
                phys = jnp.where(valid, phys, NULL_PAGE)
                ck = ck.at[phys, slot].set(
                    k.reshape(b, s, kvh * hd).astype(ck.dtype)
                )
                cv = cv.at[phys, slot].set(
                    v.reshape(b, s, kvh * hd).astype(cv.dtype)
                )
                new_cache = {"k": ck, "v": cv}
        with jax.named_scope("kv_gather"):
            kseq, vseq = _gather_kv(new_cache, page_table)
            s2 = kseq.shape[1]
            k = kseq.reshape(b, s2, kvh, hd).astype(cd)
            v = vseq.reshape(b, s2, kvh, hd).astype(cd)
        kv_len = limit
        chunk_block = page
        causal = True
    elif cache is not None and page_table is not None:
        # Paged decode: cache is the physical page pool of THIS layer,
        # (num_pages, page_size, kv_dim).  The token is scattered into
        # page_table[b, pos // page] at slot pos % page; inactive batch
        # slots carry page_table rows of null pages (page 0), so their
        # writes land in the reserved sink and the pool stays consistent.
        # The read follows ``paged_decode_route``: on a TPU the paged
        # decode kernel reads each row's live pages in the pool's stored
        # dtype; elsewhere the XLA gather (jnp.take of each sequence's
        # pages) feeds blocked_attention.
        from repro.runtime.paged_cache import (
            dequantize_kv_page,
            quantize_kv_page,
        )

        with jax.named_scope("kv_write"):
            ck, cv = cache["k"], cache["v"]
            page = ck.shape[1]
            idx = jnp.arange(b)
            pidx = (pos // page).astype(jnp.int32)
            slot = (pos % page).astype(jnp.int32)
            phys = page_table[idx, pidx]
            if quantized:
                # Decode appends one token to the tail page: dequantize that
                # page's valid rows, splice the new token in, and REQUANTIZE
                # the page with statistics over rows 0..slot.  Per-page
                # scale/shift stays exact metadata (no slot-granular state),
                # at the cost of re-rounding earlier tail-page rows - an
                # RMSE-bounded, never bit-contract-bearing path: full prompt
                # pages (the only shareable ones) are written once by prefill
                # and never pass through here.
                sl = jnp.arange(page, dtype=jnp.int32)[None, :]   # (1, page)
                is_new = (sl == slot[:, None])[..., None, None]
                valid_rows = sl <= slot[:, None]                  # (B, page)

                def requant(codes, sc, sh, new_vec):
                    old = dequantize_kv_page(
                        codes[phys].reshape(b, page, kvh, hd),
                        sc[phys], sh[phys].reshape(b, kvh, hd),
                    )                                             # f32
                    raw = jnp.where(is_new, new_vec[:, None], old)
                    qc, qs, qh = quantize_kv_page(
                        raw, valid_rows, codes.dtype,
                        scale_mode=cfg.attention.kv_quant_scale,
                    )
                    return (
                        codes.at[phys].set(qc.reshape(b, page, kvh * hd)),
                        sc.at[phys].set(qs),
                        sh.at[phys].set(qh.reshape(b, kvh * hd)),
                    )

                ck, k_scale, k_shift = requant(
                    ck, cache["k_scale"], cache["k_shift"],
                    k.reshape(b, kvh, hd).astype(jnp.float32),
                )
                cv, v_scale, v_shift = requant(
                    cv, cache["v_scale"], cache["v_shift"],
                    v.reshape(b, kvh, hd).astype(jnp.float32),
                )
                new_cache = {
                    "k": ck, "v": cv, "k_scale": k_scale, "k_shift": k_shift,
                    "v_scale": v_scale, "v_shift": v_shift,
                }
            else:
                ck = ck.at[phys, slot].set(
                    k.reshape(b, kvh * hd).astype(ck.dtype)
                )
                cv = cv.at[phys, slot].set(
                    v.reshape(b, kvh * hd).astype(cv.dtype)
                )
                new_cache = {"k": ck, "v": cv}
        kv_len = (pos + 1).astype(jnp.int32)
        causal = False  # kv_len mask subsumes causality for 1-token steps
        route = paged_decode_route(cfg, page)
        if route == "xla":
            with jax.named_scope("kv_gather"):
                kseq, vseq = _gather_kv(new_cache, page_table)
                s2 = kseq.shape[1]
                k = kseq.reshape(b, s2, kvh, hd).astype(cd)
                v = vseq.reshape(b, s2, kvh, hd).astype(cd)
        else:
            with jax.named_scope("pasa"):
                out = _paged_decode(
                    q, new_cache, page_table, kv_len, cfg,
                    interpret=route == "interpret",
                )
    elif cache is not None:
        ck, cv = cache["k"], cache["v"]
        if prefill_cache:
            with jax.named_scope("kv_write"):
                ck = jax.lax.dynamic_update_slice_in_dim(
                    ck, k.reshape(b, s_kv, kvh * hd).astype(ck.dtype), 0,
                    axis=1,
                )
                cv = jax.lax.dynamic_update_slice_in_dim(
                    cv, v.reshape(b, s_kv, kvh * hd).astype(cv.dtype), 0,
                    axis=1,
                )
            kv_len = None  # attend within the fresh k/v below, not the cache
            new_cache = {"k": ck, "v": cv}
        else:
            with jax.named_scope("kv_write"):
                idx = jnp.arange(b)
                ck = ck.at[idx, pos].set(
                    k.reshape(b, kvh * hd).astype(ck.dtype)
                )
                cv = cv.at[idx, pos].set(
                    v.reshape(b, kvh * hd).astype(cv.dtype)
                )
            new_cache = {"k": ck, "v": cv}
            with jax.named_scope("kv_gather"):
                s2 = ck.shape[1]
                k = ck.reshape(b, s2, kvh, hd).astype(cd)
                v = cv.reshape(b, s2, kvh, hd).astype(cd)
            kv_len = (pos + 1).astype(jnp.int32)
            causal = False  # kv_len mask subsumes causality for 1-token steps

    decode_path = cache is not None and not prefill_cache
    # Layout choice (EXPERIMENTS.md section Perf, iteration 1):
    #  * train/prefill: expand KV to the full H heads so q/k/v share the
    #    (B, H, S, hd) layout - all attention einsum dims are batch or
    #    contraction-local, so GSPMD keeps the whole KV-block scan
    #    collective-free.  KV expansion costs (g-1)x KV activation bytes,
    #    negligible next to the removed per-block all-reduces.
    #  * decode: grouped (B, KVH, G, 1, hd) layout - the KV cache stays at
    #    kvh heads (bandwidth = the decode bottleneck), and the tiny q makes
    #    the contraction split cheap.
    # No explicit per-head sharding constraints in either path: uneven
    # kvh-over-model constraints cause involuntary full rematerialization
    # copies (verified in the dry-run; see EXPERIMENTS.md).
    if out is None:
        with jax.named_scope("pasa"):
            if cfg.attention.expand_kv and not decode_path and g > 1:
                k = jnp.broadcast_to(
                    k[:, :, :, None], (b, k.shape[1], kvh, g, hd)
                ).reshape(b, k.shape[1], h, hd)
                v = jnp.broadcast_to(
                    v[:, :, :, None], (b, v.shape[1], kvh, g, hd)
                ).reshape(b, v.shape[1], h, hd)
                q5 = jnp.moveaxis(q, 2, 1)              # (B, H, S, hd)
                k5 = jnp.moveaxis(k, 2, 1)
                v5 = jnp.moveaxis(v, 2, 1)
                # Matching (possibly uneven) H-over-model constraints on all
                # three operands: keeps GSPMD from splitting the head_dim
                # contraction, which otherwise inserts one (B,H,S,hd)
                # all-reduce per KV block per layer (the dominant baseline
                # collective; EXPERIMENTS.md section Perf iteration 1).
                q5 = shard(q5, dp_axes(), "model", None, None)
                k5 = shard(k5, dp_axes(), "model", None, None)
                v5 = shard(v5, dp_axes(), "model", None, None)
                out_heads_axis = 1
            else:
                q5 = jnp.moveaxis(q, 2, 1).reshape(b, kvh, g, s, hd)
                k5 = jnp.moveaxis(k, 2, 1)[:, :, None]
                v5 = jnp.moveaxis(v, 2, 1)[:, :, None]
                out_heads_axis = None

            kv_len_b = None
            if kv_len is not None:
                shape = (b, 1) if out_heads_axis == 1 else (b, 1, 1)
                kv_len_b = kv_len.reshape(shape)
            q_off = None
            if pos is not None and not prefill_cache:
                q_off = pos
            elif chunk_block > 0:
                # causal q positions = pos + arange(S); shaped to broadcast as
                # (..., S1, 1) against the per-block column ids in
                # blocked_attention
                q_off = pos.reshape(
                    (b, 1, 1) if out_heads_axis == 1 else (b, 1, 1, 1)
                )
            out = _attend(
                q5, k5, v5, cfg, causal=causal, kv_len=kv_len_b,
                q_offset=q_off,
                decode=decode_path, chunk_block=chunk_block,
            )

    with jax.named_scope("attn_out"):
        out = jnp.moveaxis(
            out.reshape(b, kvh * g, s, hd), 1, 2
        ).reshape(b, s, h * hd)
        out = shard(out, dp_axes(), None, "model")
        out = L_row_parallel(out.astype(cd), p["wo"], cd)
    return out, new_cache
