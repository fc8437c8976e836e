"""Blocked online PASA / FlashAttention in pure JAX (the paper's Algorithm 1).

This module is simultaneously:
  * the faithful reference implementation of the paper (every step of
    Algorithm 1, with the paper's per-step precision annotations driven by a
    :class:`~repro.core.precision.PrecisionPolicy`),
  * the oracle the Pallas kernels are validated against, and
  * the XLA attention path used by every model in the zoo (lax.scan over KV
    blocks => no materialized S1 x S2 score matrix, which is what makes the
    32k-prefill dry-runs fit in HBM).

Layout convention: q is (..., S1, D), k/v are (..., S2, D); leading dims
broadcast (models use (B, KVH, G, S, D) vs (B, KVH, 1, S, D) for GQA).

The scan-carry state is factored out (:class:`AttnState`, :func:`update_state`)
so that the ring/sequence-parallel variant (core/ring.py) can reuse the exact
same block update across devices.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import beta as beta_lib
from repro.core.precision import (
    FP16,
    FP32,
    PrecisionPolicy,
    operand_precision,
    reduce_dtype,
)
from repro.core.shifting import (
    effective_invariance,
    shift_kv_blocks,
    shifting_matrix,
)

# Finite stand-in for -inf that survives fp16 arithmetic (|x| < 65504) and
# underflows exp() to exactly 0 in every policy.
NEG_BIG = -30000.0


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class AttnState:
    """Running softmax statistics carried across KV blocks (Algorithm 1).

    m:   running corrected max            (..., S1, 1)
    l:   running corrected sum            (..., S1, 1)
    acc: running un-normalized output     (..., S1, D)
    f:   global pseudo-average  F-bar     (..., S1, 1)   (PASA only)
    cnt: number of KV blocks folded in so far (scalar int32)
    """

    m: jnp.ndarray
    l: jnp.ndarray
    acc: jnp.ndarray
    f: jnp.ndarray
    cnt: jnp.ndarray


def init_state(
    lead, d: int, policy: PrecisionPolicy, *, per_row_cnt: bool = False
) -> AttnState:
    """``lead`` is the query shape without the head dim: (..., S1).

    ``per_row_cnt=True`` makes the folded-block counter a per-query-row
    array (the chunk-exact prefill convention, where rows of the same chunk
    fold different numbers of live blocks); the default scalar counter is
    the shared-sweep convention of decode and whole-prompt prefill.
    """
    lead = tuple(lead)
    st = policy.stat_dtype
    cnt = (
        jnp.zeros(lead + (1,), jnp.int32) if per_row_cnt
        else jnp.zeros((), jnp.int32)
    )
    return AttnState(
        m=jnp.full(lead + (1,), NEG_BIG, st),
        l=jnp.zeros(lead + (1,), st),
        acc=jnp.zeros(lead + (d,), policy.acc_dtype),
        f=jnp.zeros(lead + (1,), st),
        cnt=cnt,
    )


def _gemm_dtype(policy: PrecisionPolicy):
    # The matrix engine (MXU / CUBE) accumulates wider than its operand store;
    # the *narrow store* of the result is what the policy controls.
    return jnp.float64 if policy.score_dtype == jnp.float64 else jnp.float32


def update_state(
    state: AttnState,
    q: jnp.ndarray,
    k_shifted: jnp.ndarray,
    v: jnp.ndarray,
    *,
    inva: float,
    policy: PrecisionPolicy,
    mask: Optional[jnp.ndarray],
    post_scale: float = 1.0,
    sbar_over_mask: bool = False,
    sbar_mask: Optional[jnp.ndarray] = None,
    dead_rows_noop: bool = False,
) -> AttnState:
    """Fold one KV block into the running state (Algorithm 1 lines 11-20).

    Args:
      q: (..., S1, D) query, already in ``policy.input_dtype``.  NOT pre-scaled:
        the 1/sqrt(d) lives inside ``k_shifted`` (folded into M, Eq. 10).
      k_shifted: (..., s2, D) PASA-preprocessed key block K'_j.
      v: (..., s2, D) value block.
      inva: beta/(1-beta) (0.0 => plain FlashAttention-2; all correction terms
        vanish and this is exactly FA2's online softmax).
      mask: optional (..., S1, s2) bool, True = attend.  By default applied
        *after* the row-mean: the shift M subtracted involves all s2 columns,
        so S-bar' must also be over all s2 columns for the recovery identity
        (Eq. 14) to hold.
      sbar_over_mask: compute the row pseudo-average over the *masked* (valid)
        columns only - the decode-kernel convention, where the algebraic key
        shift also used only the valid columns of the block.  Eq. 14 holds for
        any per-block shift vector as long as the row mean is taken over the
        same column set the shift used, so both conventions are exact; this
        flag selects which one.  A fully-masked block contributes sbar = 0
        (count clamped to 1) and its exp() terms underflow to exactly 0, so
        trailing dead blocks never perturb the output.
      sbar_mask: optional (..., 1, s2) row-uniform column mask; when given it
        (not ``mask``) defines the column set of the row pseudo-average and
        the pre-GEMM value zeroing.  The chunk-exact prefill convention uses
        this to keep sbar over the *valid* (col < kv_len) columns while the
        softmax ``mask`` additionally carries per-row causal structure.
      dead_rows_noop: rows for which ``mask`` is all-False keep their state
        bit-unchanged and do not count the block (requires a per-row ``cnt``,
        see :func:`init_state`).  This makes a row's final state depend only
        on its OWN live blocks - the property that makes chunked prefill
        bit-invariant to the chunk schedule (a row folded after the chunk
        boundary moved past it sees extra fully-masked blocks, which must be
        exact no-ops, not merely exp-underflow-small perturbations of the
        rescaling chain).
    """
    st = policy.stat_dtype
    gemm_t = _gemm_dtype(policy)
    s2 = k_shifted.shape[-2]

    # -- line 11: S'_ij = Q_i K'_j^T, stored at score precision. ------------
    s = jnp.einsum(
        "...sd,...td->...st", q, k_shifted, preferred_element_type=gemm_t,
        precision=operand_precision(q.dtype),
    ).astype(policy.score_dtype)
    if post_scale != 1.0:
        # Plain-FA path (Eq. 2): static scaling happens on the vector unit
        # *after* the score store - so the raw QK^T overflow (the paper's
        # whole subject) is faithfully reproduced at fp16 score precision.
        s = s * jnp.asarray(post_scale, s.dtype)

    # -- line 13: row pseudo-average of the shifted block. ------------------
    smask = sbar_mask if sbar_mask is not None else (
        mask if sbar_over_mask else None
    )
    # Reductions accumulate at the wide dtype and round once on the store -
    # the kernels do the same (repro.core.precision.reduce_dtype).
    wide = reduce_dtype(st)
    if smask is not None:
        cnt_cols = jnp.maximum(
            jnp.sum(smask.astype(wide), axis=-1, keepdims=True), 1.0
        )
        sbar = (
            jnp.sum(jnp.where(smask, s.astype(wide), 0.0), axis=-1,
                    keepdims=True)
            / cnt_cols
        ).astype(st)
    else:
        sbar = jnp.mean(s.astype(wide), axis=-1, keepdims=True).astype(st)

    if mask is not None:
        s = jnp.where(mask, s, jnp.asarray(NEG_BIG, s.dtype))

    # -- line 12: local (uncorrected) softmax stats. -------------------------
    m_loc = jnp.max(s.astype(st), axis=-1, keepdims=True)
    p = jnp.exp(s.astype(st) - m_loc).astype(policy.score_dtype)
    if mask is not None:
        # Force masked probabilities to exactly 0 (matching the Pallas
        # kernels).  In live blocks exp(NEG_BIG - m_loc) already underflows
        # to 0, but in a FULLY-masked block m_loc == NEG_BIG makes p == 1
        # everywhere, and e_cur * (p @ v) would 0*Inf-poison the accumulator
        # if v holds non-finite stale values (recycled, unscrubbed pages).
        p = jnp.where(mask, p, jnp.asarray(0.0, p.dtype))
    l_loc = jnp.sum(p.astype(wide), axis=-1, keepdims=True).astype(st)

    first = state.cnt == 0
    if inva != 0.0:
        # -- line 14: global pseudo-average F-bar^j (running mean of sbar). --
        cntf = state.cnt.astype(st)
        f_new = (cntf * state.f + sbar) / (cntf + 1.0)
        # -- line 15: correction terms of the maximum. ------------------------
        dm_prev_c = jnp.asarray(inva, st) * (state.f - f_new)
        dm_cur_c = jnp.asarray(inva, st) * (sbar - f_new)
    else:
        f_new = state.f
        dm_prev_c = jnp.zeros_like(state.m)
        dm_cur_c = jnp.zeros_like(m_loc)

    # -- line 16: corrected running max.  Guard the empty-history candidate. -
    cand_prev = jnp.where(first, jnp.asarray(NEG_BIG, st), state.m + dm_prev_c)
    m_new = jnp.maximum(cand_prev, m_loc + dm_cur_c)
    # -- line 17: rescaling exponents (both are <= 0 by construction). -------
    dm_prev = cand_prev - m_new
    dm_cur = m_loc + dm_cur_c - m_new
    e_prev = jnp.exp(dm_prev)
    e_cur = jnp.exp(dm_cur)

    # -- line 18: corrected running sum. --------------------------------------
    l_new = e_prev * state.l + e_cur * l_loc

    # -- lines 19-20: temporary output + rescaled accumulation. ---------------
    if sbar_mask is not None:
        # Chunk-exact path: sbar_mask IS the row-uniform valid-column mask;
        # zero v at stale (invalid) columns before the PV GEMM (0 * NaN
        # protection, same rationale as the decode branch below).
        v = jnp.where(
            jnp.swapaxes(sbar_mask, -1, -2), v, jnp.asarray(0.0, v.dtype)
        )
    elif sbar_over_mask and mask is not None:
        # Decode/no-scrub path: zero v at fully-masked columns before the PV
        # GEMM.  p is 0 there, but 0 * NaN = NaN inside the contraction, so
        # non-finite stale values in recycled KV pages would otherwise
        # poison the accumulator.  (Masks here are row-uniform: the causal
        # combination is rejected up front in blocked_attention.)
        col_live = jnp.any(mask, axis=-2, keepdims=True)       # (..., 1, s2)
        v = jnp.where(
            jnp.swapaxes(col_live, -1, -2), v, jnp.asarray(0.0, v.dtype)
        )
    pv = jnp.einsum(
        "...st,...td->...sd", p, v.astype(p.dtype), preferred_element_type=gemm_t,
        precision=operand_precision(p.dtype),
    ).astype(policy.acc_dtype)
    acc_new = (
        e_prev.astype(policy.acc_dtype) * state.acc
        + e_cur.astype(policy.acc_dtype) * pv
    )

    if dead_rows_noop:
        if mask is None:
            raise ValueError("dead_rows_noop needs a mask")
        if state.cnt.ndim == 0:
            raise ValueError(
                "dead_rows_noop needs a per-row cnt "
                "(init_state(per_row_cnt=True))"
            )
        row_live = jnp.any(mask, axis=-1, keepdims=True)       # (..., S1, 1)
        return AttnState(
            m=jnp.where(row_live, m_new, state.m),
            l=jnp.where(row_live, l_new, state.l),
            acc=jnp.where(row_live, acc_new, state.acc),
            f=jnp.where(row_live, f_new, state.f),
            cnt=state.cnt + row_live.astype(jnp.int32),
        )

    return AttnState(m=m_new, l=l_new, acc=acc_new, f=f_new, cnt=state.cnt + 1)


def finalize_state(
    state: AttnState, policy: PrecisionPolicy, *, zero_empty_rows: bool = False
) -> jnp.ndarray:
    """Algorithm 1 line 22: O_i = O_i / l.

    ``zero_empty_rows=True`` (the chunk-exact path) emits 0 instead of 0/0
    for rows that never folded a live block (l == 0) - dead pad rows of a
    BATCHED multi-request prefill call (runtime/engine.py grids rows of
    several requests together and pads the grid with kv_len == 0 rows).
    This matches the Pallas paged-prefill kernel's safe-divide epilogue
    bit-for-bit; live rows (l > 0) are untouched in either mode."""
    l = state.l.astype(policy.acc_dtype)
    if zero_empty_rows:
        l = jnp.where(l > 0.0, l, jnp.asarray(1.0, policy.acc_dtype))
    return (state.acc / l).astype(policy.out_dtype)


def _pad_to_multiple(x: jnp.ndarray, block: int, axis: int):
    n = x.shape[axis]
    pad = (-n) % block
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), n


@functools.partial(
    jax.jit,
    static_argnames=(
        "beta", "policy", "block_kv", "causal", "q_offset_static",
        "use_gemm_shift", "shift_mask_valid", "chunk_exact",
    ),
)
def blocked_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    beta: float = 0.0,
    policy: PrecisionPolicy = FP32,
    block_kv: int = 128,
    causal: bool = False,
    kv_len: Optional[jnp.ndarray] = None,
    q_offset: Optional[jnp.ndarray] = None,
    q_offset_static: int = 0,
    use_gemm_shift: bool = True,
    shift_mask_valid: bool = False,
    chunk_exact: bool = False,
) -> jnp.ndarray:
    """PASA (beta>0) or FlashAttention-2 (beta==0) over KV blocks via lax.scan.

    Args:
      q: (..., S1, D); k, v: (..., S2, D) with broadcastable leading dims.
      beta: PASA shifting fraction.  0 => exact FA2.  Must be < 1.
      policy: precision allocation (Figures 1-3).
      block_kv: s2, the online block length (the paper's basic block).
      causal: lower-triangular masking with absolute positions
        ``q_pos = q_offset + arange(S1)`` vs ``kv_pos = arange(S2)``.
      kv_len: optional (...)-broadcastable active KV length (decode caches).
      q_offset: optional dynamic scalar/array query-position offset (decode).
      q_offset_static: static query offset (prefill chunking).
      use_gemm_shift: True = the paper's batched-GEMM M preprocessing
        (lines 5-7); False = algebraic (K - beta*blockmean)/sqrt(d) epilogue
        (beyond-paper TPU-optimized variant; identical math, validated equal).
      shift_mask_valid: decode-kernel ragged-tail convention - the algebraic
        key shift and the row pseudo-average use only the *valid*
        (pos < kv_len, pre-padding) columns of each block, exactly matching
        kernels/pasa_decode.py and kernels/pasa_paged_decode.py.  Requires
        ``use_gemm_shift=False`` when beta > 0 (a fixed GEMM M cannot mask).
        Both conventions are mathematically exact (Eq. 14 holds for any
        consistent per-block shift/mean pair); they differ only in rounding
        on partial tail blocks, and this flag makes the XLA path
        bit-comparable to the Pallas decode kernels.  It also makes the
        output independent of whatever stale values sit beyond kv_len, which
        is what permits KV-page reuse without scrubbing.
      chunk_exact: the chunked-prefill convention (runtime/engine.py,
        kernels/pasa_paged_prefill.py).  Extends shift_mask_valid to MANY
        query rows under causal masking: the algebraic key shift AND the row
        pseudo-average both use the valid (col < kv_len) columns - the same
        column set for every row, so Eq. 14 stays exact - while the causal
        mask is applied *after* sbar, and rows for which a block is fully
        masked skip it as an exact no-op (per-row block counter; see
        ``update_state(dead_rows_noop=...)``).  Together with page-aligned
        chunk boundaries this makes prefill outputs (and therefore the K/V
        written to cache pages) bit-invariant to the chunk schedule and to
        how much of the prompt was served from the prefix cache.  Requires
        ``use_gemm_shift=False`` when beta > 0.

    Returns:
      (..., S1, D) attention output in ``policy.out_dtype``.
    """
    if not 0.0 <= beta < 1.0:
        raise ValueError(f"beta must be in [0, 1), got {beta}")
    if chunk_exact:
        shift_mask_valid = True
    if shift_mask_valid and use_gemm_shift and beta > 0.0:
        raise ValueError(
            "shift_mask_valid needs the algebraic shift (use_gemm_shift=False)"
        )
    if shift_mask_valid and causal and not chunk_exact:
        # The recovery identity needs sbar over exactly the columns the key
        # shift used; under causal masking sbar's column set would shrink
        # per-row below the shift's valid-column set.  Decode steps pass
        # causal=False (the kv_len mask subsumes causality for one token);
        # chunked prefill passes chunk_exact=True, which keeps sbar over the
        # valid columns while masking causally afterwards.
        raise ValueError("shift_mask_valid is decode-only (causal=False)")
    d = q.shape[-1]
    s1 = q.shape[-2]
    q = q.astype(policy.input_dtype)
    k = k.astype(policy.input_dtype)
    v = v.astype(policy.input_dtype)

    k, s2_orig = _pad_to_multiple(k, block_kv, axis=-2)
    v, _ = _pad_to_multiple(v, block_kv, axis=-2)
    s2_pad = k.shape[-2]
    n_blocks = s2_pad // block_kv

    # Valid-column limit shared by the mask and (optionally) the shift.
    limit = jnp.asarray(s2_orig, jnp.int32)
    if kv_len is not None:
        limit = jnp.minimum(limit, kv_len.astype(jnp.int32))

    post_scale = 1.0
    if beta > 0.0:
        if use_gemm_shift:
            # Use the invariance the *rounded* M actually realizes (optimal
            # accuracy condition, Appendix A - see shifting.effective_invariance).
            inva = effective_invariance(block_kv, d, beta, policy.input_dtype)
            m_mat = shifting_matrix(block_kv, d, beta, dtype=policy.input_dtype)
            k = shift_kv_blocks(k, m_mat, block_kv).astype(policy.input_dtype)
        else:
            inva = beta / (1.0 - beta)
            # Algebraic shift mirrors the decode kernels bit-for-bit: wide
            # accumulate, single narrow store (see precision.reduce_dtype),
            # and the same multiply-by-reciprocal scaling expression.
            wide = reduce_dtype(policy.stat_dtype)
            scale = jnp.asarray(1.0 / np.sqrt(d), wide)
            kb = k.reshape(*k.shape[:-2], n_blocks, block_kv, d)
            if shift_mask_valid:
                cols = jnp.arange(s2_pad, dtype=jnp.int32).reshape(
                    n_blocks, block_kv
                )
                vmask = (
                    cols < jnp.reshape(limit, jnp.shape(limit) + (1, 1))
                )[..., None]                       # (..., nb, bkv, 1)
                cnt = jnp.maximum(
                    jnp.sum(vmask.astype(wide), axis=-2, keepdims=True), 1.0
                )
                mean = (
                    jnp.sum(jnp.where(vmask, kb.astype(wide), 0.0), axis=-2,
                            keepdims=True) / cnt
                )
            else:
                mean = jnp.mean(kb.astype(wide), axis=-2, keepdims=True)
            kb = (kb.astype(wide) - jnp.asarray(beta, wide) * mean) * scale
            k = kb.reshape(*k.shape).astype(policy.input_dtype)
    else:
        # Faithful plain-FA precision allocation: the first GEMM emits raw
        # QK^T at score precision; 1/sqrt(d) is applied after (Eqs. 1-2).
        inva = 0.0
        post_scale = 1.0 / float(np.sqrt(d))

    # Blocked views: (..., n_blocks, block_kv, D) -> scan axis first.
    kb = jnp.moveaxis(k.reshape(*k.shape[:-2], n_blocks, block_kv, d), -3, 0)
    vb = jnp.moveaxis(v.reshape(*v.shape[:-2], n_blocks, block_kv, d), -3, 0)

    need_mask = (
        causal or (kv_len is not None) or (s2_pad != s2_orig)
        or shift_mask_valid
    )
    q_pos = None
    if causal:
        qp = jnp.arange(s1, dtype=jnp.int32) + jnp.int32(q_offset_static)
        if q_offset is not None:
            qp = qp + q_offset.astype(jnp.int32)
        q_pos = qp[..., :, None]  # (..., S1, 1)

    # Broadcast leading dims of q against k/v once so the scan body is static.
    lead = jnp.broadcast_shapes(q.shape[:-2], k.shape[:-2])
    qs = jnp.broadcast_to(q, lead + q.shape[-2:])
    state = init_state(qs.shape[:-1], d, policy, per_row_cnt=chunk_exact)

    def body(state, inp):
        kj, vj, jidx = inp
        mask = None
        sbar_mask = None
        if need_mask:
            col = jidx * block_kv + jnp.arange(block_kv, dtype=jnp.int32)
            mask = jnp.ones((s1, block_kv), bool)
            if causal:
                mask = q_pos >= col[None, :]
            col_ok = col < jnp.reshape(limit, jnp.shape(limit) + (1, 1))
            mask = jnp.logical_and(mask, col_ok)
            if chunk_exact:
                # Shift/sbar column set = valid columns (row-uniform), the
                # causal structure lives only in the softmax mask.
                sbar_mask = col_ok
        state = update_state(
            state, qs, kj, vj, inva=inva, policy=policy, mask=mask,
            post_scale=post_scale,
            sbar_over_mask=shift_mask_valid and not chunk_exact,
            sbar_mask=sbar_mask, dead_rows_noop=chunk_exact,
        )
        return state, None

    idx = jnp.arange(n_blocks, dtype=jnp.int32)
    state, _ = jax.lax.scan(body, state, (kb, vb, idx))
    # chunk-exact: fully-dead rows (kv_len == 0 pad rows of a batched
    # multi-request prefill) emit 0, matching the Pallas kernel.
    return finalize_state(state, policy, zero_empty_rows=chunk_exact)


def pasa_attention(
    q, k, v, *, beta: float = beta_lib.DEFAULT_BETA, policy: PrecisionPolicy = FP16,
    block_kv: int = 128, **kw,
) -> jnp.ndarray:
    """The paper's headline configuration: PASA, fully-FP16 allocation."""
    return blocked_attention(
        q, k, v, beta=beta, policy=policy, block_kv=block_kv, **kw
    )


def flash_attention(
    q, k, v, *, policy: PrecisionPolicy = FP32, block_kv: int = 128, **kw
) -> jnp.ndarray:
    """FlashAttention-2 baseline (PASA with beta = 0)."""
    return blocked_attention(q, k, v, beta=0.0, policy=policy, block_kv=block_kv, **kw)
