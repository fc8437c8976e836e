"""PASA chunked prefill over PAGED KV (Pallas TPU kernel + XLA fallback).

The third member of the PASA kernel family: ``pasa_attention`` is the
whole-prompt prefill on contiguous K/V, ``pasa_paged_decode`` is one token
vs paged K/V - this kernel is a prompt *chunk* (many query rows at a
position offset) vs paged K/V, the compute engine of the chunked-prefill
scheduler (runtime/engine.py).  The chunk's own K/V are scattered into
their pages *before* the call (models/attention.py), so the kernel reads
everything - cached prefix pages and the in-flight chunk - uniformly
through the page table via scalar prefetch, exactly like the paged decode
kernel: the physical page id is resolved in the BlockSpec index map before
the DMA issues, so the gather costs no extra HBM traffic.

Numerical convention: **chunk-exact** (``core.pasa.blocked_attention``
docstring), the superset of the decode kernels' ``shift_mask_valid``:

  * per-page algebraic key shift and row pseudo-average over the *valid*
    (col < kv_len) columns - one column set for all rows, so Eq. 14 holds;
  * causal masking (absolute row position vs absolute column) applied
    after sbar;
  * rows for which a page is fully causally dead skip it as an exact
    no-op (per-row block counter in VMEM scratch), so a row's output - and
    therefore the K/V the model writes for it - is bit-invariant to the
    chunk schedule and to the page-table width.  This is the property the
    radix prefix cache's exactness argument rests on
    (runtime/prefix_cache.py): cache-hit prefill == cold prefill, bitwise.

Grid: (B * H, Nq, max_pages), pages innermost/"arbitrary"; one grid step
folds one page into the running state of one (batch, head, q-tile) cell.
A q tile skips pages wholly past the valid length AND pages wholly in its
causal future (tile-level ``pl.when``), mirroring the causal block skip of
the contiguous prefill kernel.

Multi-request batching (the engine's batched prefill, runtime/engine.py):
the B rows need not belong to one request - each row's chunk start, valid
length, and page-table row arrive through the same scalar-prefetch maps,
so one device call advances chunks of several still-prefilling requests
at once.  Ragged tails are right-padded to the (B, CS) grid; a fully-dead
pad row (``kv_len == 0``) folds no page and the final safe-divide emits
exact zeros for it - the XLA fallback mirrors this via
``finalize_state(zero_empty_rows=True)``.

Quantized pools: as in the paged decode kernel, per-page scale/shift
sidecars ride the same page-table index maps and the fp8/int8 codes are
dequantized in VMEM immediately before the chunk block update
(``kernels/pasa_paged_decode.dequant_block``); dead pages are skipped
before their sidecars are touched, so NaN-poisoned metadata on stale pages
is as inert as stale page bytes.

The XLA fallback (:func:`paged_prefill_xla`) is the gather +
``blocked_attention(chunk_exact=True)`` route - the CPU/GPU path, what the
serving engine uses off-TPU, and the oracle the kernel is validated
against (tests/test_prefix_cache.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.precision import reduce_dtype
from repro.kernels.fp16 import KernelNumerics
from repro.kernels.pasa_decode import accumulate, online_update
from repro.kernels.pasa_paged_decode import (
    _gather_dequant,
    dequant_block,
    head_scale,
    pool_blocks,
)

NEG_BIG = -30000.0
_LANES = 128


def _chunk_block_update(
    q, k, v,                  # (bq, d), (page, d), (page, d) VMEM values
    row0,                     # scalar int32: absolute position of q row 0
    col0,                     # scalar int32: absolute position of column 0
    kv_len,                   # scalar int32: valid KV length (chunk end)
    block_q: int,
    page: int,
    m_scr, l_scr, f_scr, cnt_scr, acc_scr,
    *,
    inva: float,
    beta: float,
    in_dtype,
    stat_dtype,
    acc_dtype,
    score_dtype,
    num: KernelNumerics,
):
    """Fold one page into the per-row running state (chunk-exact rules).

    Reductions accumulate at ``reduce_dtype(stat_dtype)`` and round once on
    the store (see that function's doc) - the same wide-accumulate /
    narrow-store convention as ``pasa_decode.masked_block_update``.  The
    *spelling* differs deliberately: this kernel's bit-tracking partner is
    the XLA fallback (``paged_prefill_xla`` -> ``blocked_attention``, the
    engine's CPU route and this kernel's validation oracle), so every
    reduction and the beta == 0 plain-FA post-scale use the exact
    expressions of ``pasa.update_state`` / ``blocked_attention`` - which
    makes kernel and fallback outputs bit-identical on the test workloads
    (tests/test_prefix_cache.py) instead of merely tolerance-close.  The
    decode kernels' partner is their paged/contiguous twin across memory
    layouts, hence their ones-vector ``dot_general`` spelling.
    ``num`` holds the policy dtypes (kernels/fp16.py).
    """
    d = q.shape[-1]
    wide = reduce_dtype(stat_dtype)
    scale = jnp.asarray(1.0 / np.sqrt(d), wide)

    def fit_stat(x):
        return num.fit(x, stat_dtype)

    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, (page, 1), 0)
    valid = cols < kv_len                                   # (page, 1)
    # integer-valued -> exact at wide regardless of order
    count = jnp.maximum(jnp.sum(valid.astype(wide)), 1.0)

    if beta > 0.0:
        km = jnp.sum(
            jnp.where(valid, k.astype(wide), 0.0), axis=0, keepdims=True,
        ) / count                                           # (1, d)
        k_sh = num.fit(
            (k.astype(wide) - jnp.asarray(beta, wide) * km) * scale, in_dtype
        )
    else:
        k_sh = k

    s = num.fit(jax.lax.dot_general(
        q, k_sh, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=num.precision,
    ), score_dtype)                                         # (bq, page)
    if beta == 0.0:
        # Plain-FA path (Eq. 2), mirroring the XLA fallback's update_state:
        # raw QK^T is stored at score precision (the paper's overflow point)
        # and the static 1/sqrt(d) lands after, on the vector unit.
        s = num.fit(s * num.const(1.0 / np.sqrt(d), score_dtype), score_dtype)

    vmask = valid[:, 0][None, :]                            # (1, page)
    # Row pseudo-average over the VALID columns (same set the shift used);
    # the causal mask has not been applied yet - chunk-exact semantics.
    sbar = fit_stat(
        jnp.sum(jnp.where(vmask, s.astype(wide), 0.0), axis=-1,
                keepdims=True) / count
    )

    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
    causal = rows >= jnp.transpose(cols)                    # (bq, page)
    mask = jnp.logical_and(causal, vmask)
    s = jnp.where(mask, s, jnp.asarray(NEG_BIG, s.dtype))

    s_stat = fit_stat(s)
    m_loc = jnp.max(
        s_stat.astype(num.storage(stat_dtype)), axis=-1, keepdims=True
    )
    p = num.fit(jnp.exp(fit_stat(s_stat - m_loc)), score_dtype)
    p = jnp.where(mask, p, jnp.asarray(0.0, p.dtype))
    l_loc = fit_stat(jnp.sum(p.astype(wide), axis=-1, keepdims=True))

    m_new, l_new, f_new, e_prev, e_cur = online_update(
        m_scr[:, :1], l_scr[:, :1], f_scr[:, :1],
        cnt_scr[:, :1],                                     # per-row (bq, 1)
        m_loc, l_loc, sbar, inva=inva, stat_dtype=stat_dtype, num=num,
    )

    # Zero v at INVALID columns before the PV GEMM (0 * NaN protection for
    # stale page contents); causally-masked-but-valid columns hold real
    # finite K/V and are already nulled through p == 0.
    v_live = jnp.where(valid, v, jnp.asarray(0.0, v.dtype))
    pv = num.fit(jax.lax.dot_general(
        p, num.fit(v_live, score_dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=num.precision,
    ), acc_dtype)
    acc_new = accumulate(
        acc_scr[...], e_prev, e_cur, pv, acc_dtype=acc_dtype, num=num
    )

    # Per-row dead-page no-op: rows with no causally-visible valid column
    # keep their state bit-unchanged and do not count the page.
    row_live = jnp.logical_and(rows >= col0, col0 < kv_len)  # (bq, 1)
    m_scr[...] = jnp.where(
        row_live, jnp.broadcast_to(m_new, m_scr.shape), m_scr[...]
    )
    l_scr[...] = jnp.where(
        row_live, jnp.broadcast_to(l_new, l_scr.shape), l_scr[...]
    )
    f_scr[...] = jnp.where(
        row_live, jnp.broadcast_to(f_new, f_scr.shape), f_scr[...]
    )
    acc_scr[...] = jnp.where(row_live, acc_new, acc_scr[...])
    cnt_scr[...] = cnt_scr[...] + jnp.where(
        row_live, 1.0, 0.0
    ).astype(cnt_scr.dtype)


def _paged_prefill_kernel(
    start_ref,             # scalar prefetch: (B,) int32 chunk start
    kv_len_ref,            # scalar prefetch: (B,) int32 valid KV length
    pt_ref,                # scalar prefetch: (B, max_pages) int32 page table
    *refs,
    inva: float,
    beta: float,
    n_heads: int,
    group: int,
    block_q: int,
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
        # (1,bq,D), (1,page,D) codes x2, (1,1,KVH) scale x2, (1,1,D) shift x2
        (q_ref, k_ref, v_ref, ks_ref, kh_ref, vs_ref, vh_ref,
         o_ref, m_scr, l_scr, f_scr, cnt_scr, acc_scr) = refs
    else:
        (q_ref, k_ref, v_ref,
         o_ref, m_scr, l_scr, f_scr, cnt_scr, acc_scr) = refs
    bh = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    b = bh // n_heads
    kv_head = (bh % n_heads) // group
    start = start_ref[b]
    kv_len = kv_len_ref[b]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_BIG)
        l_scr[...] = jnp.zeros_like(l_scr)
        f_scr[...] = jnp.zeros_like(f_scr)
        cnt_scr[...] = jnp.zeros_like(cnt_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Page j is dead for the whole tile iff it is past the valid length or
    # wholly in the causal future of the tile's LAST row.
    row_last = start + (i + 1) * block_q - 1
    live = jnp.logical_and(j * page_size < kv_len, j * page_size <= row_last)

    @pl.when(live)
    def _step():
        k = k_ref[0]
        v = v_ref[0]
        if quantized:
            k = dequant_block(
                k, head_scale(ks_ref[0], kv_head), kh_ref[0], deq_dtype, num
            )
            v = dequant_block(
                v, head_scale(vs_ref[0], kv_head), vh_ref[0], deq_dtype, num
            )
        _chunk_block_update(
            q_ref[0], k, v,
            start + i * block_q, j * page_size, kv_len,
            block_q, page_size,
            m_scr, l_scr, f_scr, cnt_scr, acc_scr,
            inva=inva, beta=beta, in_dtype=deq_dtype, stat_dtype=stat_dtype,
            acc_dtype=acc_dtype, score_dtype=score_dtype, num=num,
        )

    @pl.when(j == n_pages - 1)
    def _fin():
        l = num.fit(l_scr[:, :1], acc_dtype)
        # Rows past the real chunk never fold a block (l == 0); emit 0
        # instead of 0/0 so pad rows cannot NaN-poison downstream layers.
        safe = jnp.where(l > 0.0, l, jnp.asarray(1.0, l.dtype))
        o_ref[0] = num.fit(acc_scr[...] / safe, out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "inva", "beta", "block_q", "stat_dtype", "acc_dtype", "score_dtype",
        "out_dtype", "deq_dtype", "interpret",
    ),
)
def paged_prefill_kernel_call(
    q: jnp.ndarray,          # (B, H, CS, D) chunk queries, full query heads
    k_pages: jnp.ndarray,    # (P, page, KVH, D) physical pool (raw or codes)
    v_pages: jnp.ndarray,    # (P, page, KVH, D)
    page_table: jnp.ndarray, # (B, max_pages) int32
    chunk_start: jnp.ndarray,  # (B,) int32 absolute position of q row 0
    kv_len: jnp.ndarray,     # (B,) int32 valid length (chunk end)
    *,
    inva: float,
    beta: float,
    k_scale=None,            # (P, KVH) f32     } quantized-pool sidecars;
    k_shift=None,            # (P, KVH, D) f32  } all four or none
    v_scale=None,
    v_shift=None,
    block_q: int = 128,
    stat_dtype=jnp.float32,
    acc_dtype=jnp.float32,
    score_dtype=jnp.float16,
    out_dtype=jnp.float16,
    deq_dtype=jnp.float16,
    interpret: bool = False,
) -> jnp.ndarray:
    b, h, cs, d = q.shape
    _, page_size, kvh, _ = k_pages.shape
    if h % kvh:
        raise ValueError(f"q heads {h} not a multiple of kv heads {kvh}")
    group = h // kvh
    if cs % block_q:
        raise ValueError(f"chunk {cs} % block_q {block_q} != 0 (pad upstream)")
    n_q = cs // block_q
    n_pages = page_table.shape[1]
    quantized = k_scale is not None
    num = KernelNumerics(emulate_f16=not interpret)

    qr = num.to_storage(q.reshape(b * h, cs, d))

    kernel = functools.partial(
        _paged_prefill_kernel,
        inva=inva, beta=beta, n_heads=h, group=group, block_q=block_q,
        page_size=page_size, n_pages=n_pages,
        stat_dtype=stat_dtype, acc_dtype=acc_dtype, score_dtype=score_dtype,
        out_dtype=out_dtype, quantized=quantized, deq_dtype=deq_dtype,
        num=num,
    )

    def q_map(bh, i, j, st, kvl, pt):
        return (bh, i, 0)

    def page_map(bh, i, j, st, kvl, pt):
        # page gather: physical id from the prefetched table, before DMA
        return (pt[bh // h, j], (bh % h) // group)

    quant = (
        dict(k_scale=k_scale, k_shift=k_shift, v_scale=v_scale,
             v_shift=v_shift) if quantized else None
    )
    kv_specs, kv_inputs = pool_blocks(
        num.to_storage(k_pages), num.to_storage(v_pages), quant, page_map
    )
    in_specs = [pl.BlockSpec((1, block_q, d), q_map)] + kv_specs
    inputs = [qr] + kv_inputs

    stat = num.storage(stat_dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b * h, n_q, n_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_q, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), stat),   # m
            pltpu.VMEM((block_q, _LANES), stat),   # l
            pltpu.VMEM((block_q, _LANES), stat),   # f
            pltpu.VMEM((block_q, _LANES), stat),   # per-row block count
            pltpu.VMEM((block_q, d), num.storage(acc_dtype)),  # accumulator
        ],
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (b * h, cs, d), num.storage(out_dtype)
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(
        chunk_start.astype(jnp.int32), kv_len.astype(jnp.int32),
        page_table.astype(jnp.int32),
        *inputs,
    )
    return out.reshape(b, h, cs, d).astype(out_dtype)


def paged_prefill_xla(
    q: jnp.ndarray,          # (B, H, CS, D)
    k_pages: jnp.ndarray,    # (P, page, KVH, D)
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray, # (B, max_pages)
    chunk_start: jnp.ndarray,  # (B,)
    kv_len: jnp.ndarray,     # (B,)
    *,
    beta: float,
    policy,
    k_scale=None,
    k_shift=None,
    v_scale=None,
    v_shift=None,
) -> jnp.ndarray:
    """Gather-then-attend fallback at the chunk-exact convention.

    ``jnp.take`` of the pages (+ sidecar dequantization for quantized
    pools) + ``blocked_attention(chunk_exact=True)`` with block granularity
    == page size, so the XLA shift/sbar column sets match the kernel's
    page-local ones.  The engine's CPU route and the kernel's validation
    oracle."""
    from repro.core.pasa import blocked_attention

    b, h, cs, d = q.shape
    _, page, kvh, _ = k_pages.shape
    group = h // kvh
    ks = _gather_dequant(
        k_pages, k_scale, k_shift, page_table, policy.input_dtype
    )
    vs = _gather_dequant(
        v_pages, v_scale, v_shift, page_table, policy.input_dtype
    )
    ks = jnp.moveaxis(ks, 2, 1)                      # (B, KVH, S2v, D)
    vs = jnp.moveaxis(vs, 2, 1)
    qg = q.reshape(b, kvh, group, cs, d)
    out = blocked_attention(
        qg, ks[:, :, None], vs[:, :, None],
        beta=beta, policy=policy, block_kv=page, causal=True,
        kv_len=kv_len.reshape(b, 1, 1),
        q_offset=chunk_start.reshape(b, 1, 1, 1),
        use_gemm_shift=False, chunk_exact=True,
    )
    return out.reshape(b, h, cs, d)
