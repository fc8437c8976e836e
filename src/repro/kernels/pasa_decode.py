"""PASA flash-decode Pallas kernel (single new token vs a long KV cache).

TPU adaptations (DESIGN.md section 2):

  * **GQA group-as-rows**: the (tiny) per-step query for one KV head is the
    (group, d) matrix of its grouped query heads, so the score GEMM is
    (group x d) @ (d x block_kv) - the group dimension feeds the MXU's rows
    instead of wasting them on a single query row.
  * **Algebraic shifting**: decode is HBM-bandwidth-bound on the cache read;
    recomputing K' = M K per step would re-do the prefill GEMM every token.
    Instead the kernel subtracts beta * (masked block mean) inline - the same
    math (Eq. 11 right-hand side), validated equal to the GEMM form.  The
    block mean uses only the *valid* (pos < kv_len) columns, and the
    recovery divides the masked row-sum by the same count, so Eq. 14 holds
    exactly for the ragged tail block.
  * kv_len arrives via scalar prefetch so the index map / masking see it
    before the DMA pipeline issues.

Grid: (B, KVH, Nkv) with Nkv innermost/arbitrary.
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

NEG_BIG = -30000.0
_LANES = 128


def init_decode_scratch(m_scr, l_scr, f_scr, cnt_scr, acc_scr):
    """Reset the online-softmax running state at the start of a KV sweep."""
    m_scr[...] = jnp.full_like(m_scr, NEG_BIG)
    l_scr[...] = jnp.zeros_like(l_scr)
    f_scr[...] = jnp.zeros_like(f_scr)
    cnt_scr[0, 0] = 0
    acc_scr[...] = jnp.zeros_like(acc_scr)


def decode_scratch(rows, d, stat_dtype, acc_dtype, num: KernelNumerics):
    """Running decode state of ``rows`` query rows: m, l, F-bar
    (lane-replicated), the processed block count, and the (rows, d) output
    accumulator."""
    stat = num.storage(stat_dtype)
    return [
        pltpu.VMEM((rows, _LANES), stat),
        pltpu.VMEM((rows, _LANES), stat),
        pltpu.VMEM((rows, _LANES), stat),
        pltpu.SMEM((1, 1), jnp.int32),
        pltpu.VMEM((rows, d), num.storage(acc_dtype)),
    ]


def masked_block_update(
    q,                     # (R, d) query rows of H kv heads, head-major
    ks, vs,                # H (block, d) key and value blocks at in_dtype
    kv_len,                # scalar int32 valid length of this sequence
    col0,                  # first global column of this block (j * block)
    block: int,
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
    """Fold one KV block of each of H kv heads into the running decode
    state (shared kernel body).

    The algebraic-shift/masked-mean update of the module doc: per-block key
    mean and row pseudo-average over the *valid* (col < kv_len) columns
    only.  Used bit-identically by the contiguous decode kernel (one head,
    block == block_kv) and the paged decode kernel (every kv head of a
    page, block == page_size) - keeping this in ONE place is what makes the
    two kernels' outputs comparable bit-for-bit (tests/test_paged.py).

    Rows h * G .. h * G + G - 1 of ``q`` and of the state belong to head
    h.  Only the key shift and the two block contractions run per head;
    their rows are stacked, so the softmax statistics and the online
    update - a long chain of small fp16-rounded steps - run once for all
    heads' rows.

    Reductions (count, key mean, row mean, softmax sum) accumulate at the
    wide dtype and round once on the store - see
    ``repro.core.precision.reduce_dtype``.  Accumulating them at an fp16
    ``stat_dtype`` is order-sensitive: the Mosaic lowering and the XLA
    reference round the *same* expressions differently (observed 3e-3 on
    decode outputs), which breaks the kernel==reference contract.  They
    are vector-unit sums over a (block, d) or (R, block) value, which both
    kernels hand this function in one shape and tiling; a ones-vector
    contraction would cost the matrix unit a whole pass over the block.

    ``num`` holds the policy dtypes (kernels/fp16.py): every named
    quantity below is stored at its policy dtype through ``num.fit``.
    """
    rows, d = q.shape
    g = rows // len(ks)
    wide = reduce_dtype(stat_dtype)
    scale = jnp.asarray(1.0 / np.sqrt(d), wide)

    def fit_stat(x):
        return num.fit(x, stat_dtype)

    def per_head(lhs, blocks, dims):
        """Head h's rows of ``lhs`` contracted with ``blocks[h]``, stacked."""
        return jnp.concatenate([
            jax.lax.dot_general(
                lhs[h * g:(h + 1) * g], block, dims,
                preferred_element_type=jnp.float32, precision=num.precision,
            )
            for h, block in enumerate(blocks)
        ], axis=0)

    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0)
    valid = cols < kv_len                              # (block, 1)
    # integer-valued -> exact at wide regardless of order
    count = jnp.sum(valid.astype(wide))

    def shifted(k):
        if beta <= 0.0:
            return num.fit(k.astype(wide) * scale, in_dtype)
        # Masked per-block key mean (algebraic shift; see module doc).
        km = jnp.sum(
            jnp.where(valid, k.astype(wide), 0.0), axis=0, keepdims=True
        ) / count                                      # (1, d)
        return num.fit(
            (k.astype(wide) - jnp.asarray(beta, wide) * km) * scale, in_dtype
        )

    s = num.fit(per_head(
        q, [shifted(k) for k in ks], (((1,), (1,)), ((), ()))
    ), score_dtype)                                    # (R, block)

    # The same mask along lanes, built as a row: ``valid`` turned into a
    # row gets a Mosaic layout that takes a vector register per 8 scores
    # of every (R, block) value it touches (8x the kernel's time).
    vmask = col0 + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
    vmask = vmask < kv_len                             # (1, block)
    # Masked row mean over the *valid* columns only (matches the shift).
    sbar = fit_stat(
        jnp.sum(jnp.where(vmask, s.astype(wide), 0.0), axis=1, keepdims=True)
        / count
    )                                                  # (R, 1)
    s = jnp.where(vmask, s, jnp.asarray(NEG_BIG, s.dtype))

    s_stat = fit_stat(s)
    m_loc = jnp.max(
        s_stat.astype(num.storage(stat_dtype)), axis=-1, keepdims=True
    )
    p = num.fit(jnp.exp(fit_stat(s_stat - m_loc)), score_dtype)
    p = jnp.where(vmask, p, jnp.asarray(0.0, p.dtype))
    l_loc = fit_stat(
        jnp.sum(p.astype(wide), axis=1, keepdims=True)
    )                                                  # (R, 1)

    cnt = cnt_scr[0, 0]
    m_new, l_new, f_new, e_prev, e_cur = online_update(
        m_scr[:, :1], l_scr[:, :1], f_scr[:, :1], cnt, m_loc, l_loc, sbar,
        inva=inva, stat_dtype=stat_dtype, num=num,
    )
    if inva != 0.0:
        f_scr[...] = jnp.broadcast_to(f_new, f_scr.shape)

    def live(v):
        # Zero v at invalid columns BEFORE the PV GEMM: p is already 0
        # there, but 0 * NaN = NaN inside the contraction, so non-finite
        # stale values in recycled (unscrubbed) pages would poison pv.
        v = jnp.where(valid, v, jnp.asarray(0.0, v.dtype))
        if np.dtype(score_dtype) != np.dtype(in_dtype):
            v = num.fit(v, score_dtype)
        return v

    pv = num.fit(per_head(
        p, [live(v) for v in vs], (((1,), (0,)), ((), ()))
    ), acc_dtype)                                      # (R, d)
    acc_scr[...] = accumulate(
        acc_scr[...], e_prev, e_cur, pv, acc_dtype=acc_dtype, num=num
    )
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)
    cnt_scr[0, 0] = cnt + 1


def online_update(m_prev, l_prev, f_prev, cnt, m_loc, l_loc, sbar, *,
                  inva: float, stat_dtype, num: KernelNumerics):
    """Fold one block's max ``m_loc``, sum ``l_loc`` and row pseudo-average
    ``sbar`` into the running (m, l, F-bar) state with the paper's online
    shift correction (Algorithm 1 lines 15-20); shared by every kernel
    body.  ``cnt`` counts the blocks folded before this one.

    Returns ``(m_new, l_new, f_new, e_prev, e_cur)``: e_prev rescales the
    running accumulator and e_cur this block's PV product."""

    def fit_stat(x):
        return num.fit(x, stat_dtype)

    if inva != 0.0:
        cntf = fit_stat(cnt)
        f_new = fit_stat(
            fit_stat(fit_stat(cntf * f_prev) + sbar) / fit_stat(cntf + 1.0)
        )
        inva_c = num.const(inva, stat_dtype)
        dm_prev_c = fit_stat(inva_c * fit_stat(f_prev - f_new))
        dm_cur_c = fit_stat(inva_c * fit_stat(sbar - f_new))
    else:
        f_new = f_prev
        dm_prev_c = jnp.zeros_like(m_prev)
        dm_cur_c = jnp.zeros_like(m_loc)

    cand_prev = jnp.where(
        cnt == 0, num.const(NEG_BIG, stat_dtype),
        fit_stat(m_prev + dm_prev_c),
    )
    cand_cur = fit_stat(m_loc + dm_cur_c)
    m_new = jnp.maximum(cand_prev, cand_cur)
    e_prev = fit_stat(jnp.exp(fit_stat(cand_prev - m_new)))
    e_cur = fit_stat(jnp.exp(fit_stat(cand_cur - m_new)))
    l_new = fit_stat(fit_stat(e_prev * l_prev) + fit_stat(e_cur * l_loc))
    return m_new, l_new, f_new, e_prev, e_cur


def accumulate(acc, e_prev, e_cur, pv, *, acc_dtype, num: KernelNumerics):
    """``e_prev * acc + e_cur * pv`` at the accumulator dtype (shared by
    every PASA kernel body)."""

    def fit_acc(x):
        return num.fit(x, acc_dtype)

    return fit_acc(
        fit_acc(fit_acc(e_prev) * acc) + fit_acc(fit_acc(e_cur) * pv)
    )


def _decode_kernel(
    kv_len_ref,            # scalar prefetch: (B,) int32
    q_ref, k_ref, v_ref,   # (1,1,G,D), (1,1,bkv,D), (1,1,bkv,D)
    o_ref,                 # (1,1,G,D)
    m_scr, l_scr, f_scr, cnt_scr, acc_scr,
    *,
    inva: float,
    beta: float,
    block_kv: int,
    n_kv: int,
    in_dtype,
    stat_dtype,
    acc_dtype,
    score_dtype,
    out_dtype,
    num: KernelNumerics,
):
    b = pl.program_id(0)
    j = pl.program_id(2)
    kv_len = kv_len_ref[b]

    @pl.when(j == 0)
    def _init():
        init_decode_scratch(m_scr, l_scr, f_scr, cnt_scr, acc_scr)

    @pl.when(j * block_kv < kv_len)
    def _step():
        masked_block_update(
            q_ref[0, 0], [k_ref[0, 0]], [v_ref[0, 0]],
            kv_len, j * block_kv, block_kv,
            m_scr, l_scr, f_scr, cnt_scr, acc_scr,
            inva=inva, beta=beta, in_dtype=in_dtype, stat_dtype=stat_dtype,
            acc_dtype=acc_dtype, score_dtype=score_dtype, num=num,
        )

    @pl.when(j == n_kv - 1)
    def _fin():
        l = num.fit(l_scr[:, :1], acc_dtype)
        o_ref[0, 0] = num.fit(acc_scr[...] / l, out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "inva", "beta", "block_kv", "stat_dtype", "acc_dtype", "score_dtype",
        "out_dtype", "interpret",
    ),
)
def decode_kernel_call(
    q: jnp.ndarray,        # (B, KVH, G, D) - one new token, grouped heads
    k_cache: jnp.ndarray,  # (B, KVH, S2, D) raw (unshifted) cache, zero-padded
    v_cache: jnp.ndarray,  # (B, KVH, S2, D)
    kv_len: jnp.ndarray,   # (B,) int32 valid lengths
    *,
    inva: float,
    beta: float,
    block_kv: int = 256,
    stat_dtype=jnp.float32,
    acc_dtype=jnp.float32,
    score_dtype=jnp.float16,
    out_dtype=jnp.float16,
    interpret: bool = False,
) -> jnp.ndarray:
    b, kvh, g, d = q.shape
    s2 = k_cache.shape[2]
    if s2 % block_kv:
        # Pad the cache view to the block granule instead of erroring: the
        # kv_len masking already treats every pos >= kv_len as invalid, so a
        # zero tail changes nothing (the padded columns never enter the
        # masked block mean, the row mean, or the softmax).  This copies the
        # whole cache per call - a documented SLOW path for ad-hoc shapes;
        # serving loops should allocate block-aligned caches once at init.
        pad = block_kv - s2 % block_kv
        widths = ((0, 0), (0, 0), (0, pad), (0, 0))
        k_cache = jnp.pad(k_cache, widths)
        v_cache = jnp.pad(v_cache, widths)
        s2 += pad
    n_kv = s2 // block_kv
    num = KernelNumerics(emulate_f16=not interpret)

    kernel = functools.partial(
        _decode_kernel,
        inva=inva, beta=beta, block_kv=block_kv, n_kv=n_kv,
        in_dtype=q.dtype, stat_dtype=stat_dtype, acc_dtype=acc_dtype,
        score_dtype=score_dtype, out_dtype=out_dtype, num=num,
    )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, kvh, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda b_, h, j, kvl: (b_, h, 0, 0)),
            pl.BlockSpec((1, 1, block_kv, d), lambda b_, h, j, kvl: (b_, h, j, 0)),
            pl.BlockSpec((1, 1, block_kv, d), lambda b_, h, j, kvl: (b_, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), lambda b_, h, j, kvl: (b_, h, 0, 0)),
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
        kv_len.astype(jnp.int32), num.to_storage(q),
        num.to_storage(k_cache), num.to_storage(v_cache),
    )
    return out.astype(out_dtype)
