"""PASA flash-decode over a PAGED KV cache (Pallas TPU kernel + XLA fallback).

Extends ``pasa_decode.py`` (contiguous cache) to non-contiguous fixed-size
pages: the per-sequence page table arrives via **scalar prefetch**, so the
K/V BlockSpec index maps can translate the logical page index ``j`` of the
grid into a physical page id *before* the DMA pipeline issues - the gather
costs zero extra HBM traffic versus the contiguous kernel (each page is
fetched exactly once, straight into VMEM).

Algorithm identity: one grid step processes one page.  Because the engine
fixes ``page_size`` to the PASA block length, the kernel body is the same
algebraic-shift/masked-mean block update as the contiguous decode kernel
(module doc there): the per-page key mean uses only valid (pos < kv_len)
columns, the row pseudo-average S-bar is over the same columns, and the
running (m, l, F-bar, acc) state lives in VMEM scratch across the page sweep.
Stale contents of recycled pages beyond ``kv_len`` are therefore
mathematically inert - no page scrubbing on free.

Pages fully past ``kv_len`` are skipped via ``pl.when`` (their page-table
entries point at the null page 0, a valid DMA target); valid pages of a
sequence always form a prefix of its page table.

Quantized pools (``runtime/paged_cache.py``): when the per-page sidecar
arrays (scale/shift) are passed, the K/V blocks arrive as fp8/int8 codes
and are dequantized **in VMEM** (``codes * scale + shift``, one scalar
scale and one head_dim shift vector per (page, kv-head), fetched through
the same page-table index maps) right before the shared block update - the
HBM read is 8-bit, and the shift-centered values never exist at high
precision outside the kernel.  Pages past ``kv_len`` are skipped before
their (possibly NaN-poisoned) sidecars are ever used.

Grid: (B, KVH, max_pages) with the page dimension innermost/"arbitrary".

The XLA fallback (:func:`paged_decode_xla`) is a ``jnp.take`` gather of the
pages followed by ``core.pasa.blocked_attention`` at the matching
``shift_mask_valid`` convention - the CPU/GPU route, and the oracle the
kernel is validated against (tests/test_paged.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fp16 import KernelNumerics
from repro.kernels.pasa_decode import (
    decode_scratch,
    init_decode_scratch,
    masked_block_update,
)


def dequant_block(codes, scale, shift, deq_dtype, num: KernelNumerics):
    """VMEM dequantization: (page, D) codes x scalar scale x (1, D) shift
    -> (page, D) values at the kernels' input dtype.  Element-wise and
    deterministic, so the Pallas kernels and the XLA gather fallbacks
    produce bit-identical dequantized values from the same page bytes."""
    return num.fit(codes.astype(jnp.float32) * scale + shift, deq_dtype)


def pool_blocks(k_pages, v_pages, quant, page_map):
    """Lay the pool and its sidecars out for lane-aligned page blocks.

    The pool (P, page, KVH, D) is viewed as (P, page, KVH * D), so one
    (page, D) block of kv head ``h`` is lane block ``h`` - a slice the TPU
    tiling accepts (a block of 1 along the KVH axis would sit in the
    second-minor position).  Scales (P, KVH) become (P, 1, KVH) rows read
    whole and picked by :func:`head_scale`; shifts (P, KVH, D) become
    (P, 1, KVH * D) with the same lane-block index as the pages.
    ``page_map(*grid_and_prefetch) -> (physical page, kv head)``.
    Returns (in_specs, inputs) for the K/V blocks and, when ``quant`` is
    given, the k_scale/k_shift/v_scale/v_shift blocks.
    """
    p, page, kvh, d = k_pages.shape

    def kv_map(*idx):
        pg, h = page_map(*idx)
        return (pg, 0, h)

    def sc_map(*idx):
        pg, _ = page_map(*idx)
        return (pg, 0, 0)

    kv_spec = pl.BlockSpec((1, page, d), kv_map)
    in_specs = [kv_spec, kv_spec]
    inputs = [
        k_pages.reshape(p, page, kvh * d), v_pages.reshape(p, page, kvh * d)
    ]
    if quant:
        sc_spec = pl.BlockSpec((1, 1, kvh), sc_map)
        sh_spec = pl.BlockSpec((1, 1, d), kv_map)
        in_specs += [sc_spec, sh_spec, sc_spec, sh_spec]
        inputs += [
            quant["k_scale"].reshape(p, 1, kvh),
            quant["k_shift"].reshape(p, 1, kvh * d),
            quant["v_scale"].reshape(p, 1, kvh),
            quant["v_shift"].reshape(p, 1, kvh * d),
        ]
    return in_specs, inputs


def head_scale(row, h):
    """Scale of kv head ``h`` from a (1, KVH) sidecar row, as (1, 1).

    A masked lane sum: exactly one term is non-zero, so the pick is
    exact."""
    lane = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    return jnp.sum(
        jnp.where(lane == h, row, 0.0), axis=-1, keepdims=True,
        dtype=jnp.float32,
    )


def _paged_decode_kernel(
    kv_len_ref,            # scalar prefetch: (B,) int32
    pt_ref,                # scalar prefetch: (B, max_pages) int32 page table
    *refs,
    inva: float,
    beta: float,
    page_size: int,
    n_pages: int,
    stat_dtype,
    acc_dtype,
    score_dtype,
    out_dtype,
    quantized: bool,
    deq_dtype,
    num: KernelNumerics,
):
    if quantized:
        # (1,1,G,D), (1,page,D) codes x2, (1,1,KVH) scale x2, (1,1,D) shift x2
        (q_ref, k_ref, v_ref, ks_ref, kh_ref, vs_ref, vh_ref,
         o_ref, m_scr, l_scr, f_scr, cnt_scr, acc_scr) = refs
    else:
        (q_ref, k_ref, v_ref,
         o_ref, m_scr, l_scr, f_scr, cnt_scr, acc_scr) = refs
    b = pl.program_id(0)
    h = pl.program_id(1)
    j = pl.program_id(2)
    kv_len = kv_len_ref[b]

    @pl.when(j == 0)
    def _init():
        init_decode_scratch(m_scr, l_scr, f_scr, cnt_scr, acc_scr)

    @pl.when(j * page_size < kv_len)
    def _step():
        # One page == one PASA block: the shared block update (the SAME
        # code the contiguous decode kernel runs, see pasa_decode.py) with
        # the page's global column offset.
        k = k_ref[0]
        v = v_ref[0]
        if quantized:
            k = dequant_block(
                k, head_scale(ks_ref[0], h), kh_ref[0], deq_dtype, num
            )
            v = dequant_block(
                v, head_scale(vs_ref[0], h), vh_ref[0], deq_dtype, num
            )
        masked_block_update(
            q_ref[0, 0], k, v,
            kv_len, j * page_size, page_size,
            m_scr, l_scr, f_scr, cnt_scr, acc_scr,
            inva=inva, beta=beta, in_dtype=deq_dtype, stat_dtype=stat_dtype,
            acc_dtype=acc_dtype, score_dtype=score_dtype, num=num,
        )

    @pl.when(j == n_pages - 1)
    def _fin():
        l = num.fit(l_scr[:, :1], acc_dtype)
        o_ref[0, 0] = num.fit(acc_scr[...] / l, out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "inva", "beta", "stat_dtype", "acc_dtype", "score_dtype",
        "out_dtype", "deq_dtype", "interpret",
    ),
)
def paged_decode_kernel_call(
    q: jnp.ndarray,          # (B, KVH, G, D) - one new token, grouped heads
    k_pages: jnp.ndarray,    # (P, page, KVH, D) physical page pool (raw or
    v_pages: jnp.ndarray,    # (P, page, KVH, D)   quantized codes)
    page_table: jnp.ndarray, # (B, max_pages) int32 physical page ids
    kv_len: jnp.ndarray,     # (B,) int32 valid lengths
    *,
    inva: float,
    beta: float,
    k_scale=None,            # (P, KVH) f32     } quantized-pool sidecars;
    k_shift=None,            # (P, KVH, D) f32  } all four or none
    v_scale=None,
    v_shift=None,
    stat_dtype=jnp.float32,
    acc_dtype=jnp.float32,
    score_dtype=jnp.float16,
    out_dtype=jnp.float16,
    deq_dtype=jnp.float16,
    interpret: bool = False,
) -> jnp.ndarray:
    b, kvh, g, d = q.shape
    _, page_size, _, _ = k_pages.shape
    n_pages = page_table.shape[1]
    quantized = k_scale is not None
    num = KernelNumerics(emulate_f16=not interpret)

    kernel = functools.partial(
        _paged_decode_kernel,
        inva=inva, beta=beta, page_size=page_size, n_pages=n_pages,
        stat_dtype=stat_dtype, acc_dtype=acc_dtype, score_dtype=score_dtype,
        out_dtype=out_dtype, quantized=quantized, deq_dtype=deq_dtype,
        num=num,
    )

    # The page gather: physical page id read from the prefetched table
    # inside the index map, before the DMA is issued.  Sidecars ride the
    # same gather; one (scalar, vector) pair per (page, kv-head).
    quant = (
        dict(k_scale=k_scale, k_shift=k_shift, v_scale=v_scale,
             v_shift=v_shift) if quantized else None
    )
    kv_specs, kv_inputs = pool_blocks(
        num.to_storage(k_pages), num.to_storage(v_pages), quant,
        lambda b_, h, j, kvl, pt: (pt[b_, j], h),
    )
    in_specs = [
        pl.BlockSpec((1, 1, g, d), lambda b_, h, j, kvl, pt: (b_, h, 0, 0)),
    ] + kv_specs
    inputs = [num.to_storage(q)] + kv_inputs

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kvh, n_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, g, d), lambda b_, h, j, kvl, pt: (b_, h, 0, 0)
        ),
        scratch_shapes=decode_scratch(g, d, stat_dtype, acc_dtype, num),
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, d), num.storage(out_dtype)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(
        kv_len.astype(jnp.int32), page_table.astype(jnp.int32),
        *inputs,
    )
    return out.astype(out_dtype)


def _gather_dequant(pages, scale, shift, page_table, deq_dtype):
    """XLA-side page gather + dequantization to (B, S2v, KVH, D).

    Same ``codes * scale + shift`` epilogue as :func:`dequant_block` (and
    the same fp32 intermediate), so the fallback's dequantized values are
    bit-identical to the kernel's."""
    b, mp = page_table.shape
    _, page, kvh, d = pages.shape
    flat = page_table.reshape(-1)
    codes = jnp.take(pages, flat, axis=0).reshape(b, mp, page, kvh, d)
    if scale is None:
        return codes.reshape(b, mp * page, kvh, d)
    sc = jnp.take(scale, flat, axis=0).reshape(b, mp, 1, kvh, 1)
    sh = jnp.take(shift, flat, axis=0).reshape(b, mp, 1, kvh, d)
    out = (codes.astype(jnp.float32) * sc + sh).astype(deq_dtype)
    return out.reshape(b, mp * page, kvh, d)


def paged_decode_xla(
    q: jnp.ndarray,          # (B, KVH, G, D)
    k_pages: jnp.ndarray,    # (P, page, KVH, D)
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray, # (B, max_pages)
    kv_len: jnp.ndarray,     # (B,)
    *,
    beta: float,
    policy,
    block_kv: int,
    k_scale=None,
    k_shift=None,
    v_scale=None,
    v_shift=None,
) -> jnp.ndarray:
    """Gather-then-attend fallback: ``jnp.take`` of the pages (+ sidecar
    dequantization for quantized pools) + the shift_mask_valid blocked
    attention.  Bit-matches the dense decode path when the page contents
    agree (tests/test_paged.py) and serves as the validation oracle for
    the Pallas kernel."""
    from repro.core.pasa import blocked_attention

    b, kvh, g, d = q.shape
    ks = _gather_dequant(
        k_pages, k_scale, k_shift, page_table, policy.input_dtype
    )
    vs = _gather_dequant(
        v_pages, v_scale, v_shift, page_table, policy.input_dtype
    )
    ks = jnp.moveaxis(ks, 2, 1)                      # (B, KVH, S2v, D)
    vs = jnp.moveaxis(vs, 2, 1)
    # kv_len rank must equal q's leading rank (B, KVH) for the in-scan mask
    # and the shift's valid-column mask to broadcast consistently.
    return blocked_attention(
        q, ks, vs, beta=beta, policy=policy, block_kv=block_kv,
        causal=False, kv_len=kv_len.reshape(b, 1),
        use_gemm_shift=False, shift_mask_valid=True,
    )
