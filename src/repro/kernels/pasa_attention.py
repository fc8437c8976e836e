"""Fused PASA FlashAttention Pallas TPU kernel (prefill / cross-attention).

TPU adaptation of the paper's Algorithm 1 (see DESIGN.md section 2):

  * grid = (batch*q_heads, Nq, Nkv) with the KV dimension innermost and
    "arbitrary" semantics - the running state (m, l, F-bar, acc) lives in VMEM
    scratch across the KV sweep of one (bh, i) cell.
  * Q/K'/V tiles are (block_q, d) / (block_kv, d) VMEM blocks; all matmul dims
    are kept multiples of the 128-lane MXU tiling by choosing block sizes.
  * softmax statistics are stored as (block_q, 128) lane-replicated tiles
    (TPU vregs are 8x128; this is the standard Pallas flash-attention layout).
  * the shifting GEMM (Algorithm 1 lines 5-7) is a separate batched pass
    (kernels/shift_kv.py), exactly like the paper's pre-processing loop; this
    kernel consumes the already-shifted K'.
  * GQA: the K/V index map folds the query head onto its KV head
    (kvh = qh // group), so grouped heads reuse the same K'/V tiles.

The kernel is parameterized by ``inva`` (beta/(1-beta) realized by the stored
M - see core/shifting.effective_invariance).  ``inva = 0`` plus
``post_scale = 1/sqrt(d)`` yields the plain FlashAttention-2 baseline kernel
(kernels/flash_attention.py) on the identical tiling, which is what the
paper's performance comparison isolates.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.precision import reduce_dtype
from repro.kernels.fp16 import KernelNumerics
from repro.kernels.pasa_decode import accumulate, online_update

NEG_BIG = -30000.0
_LANES = 128


def _attn_kernel(
    q_ref, k_ref, v_ref,  # inputs
    o_ref,                # output
    m_scr, l_scr, f_scr, cnt_scr, acc_scr,  # scratch
    *,
    inva: float,
    post_scale: float,
    causal: bool,
    block_q: int,
    block_kv: int,
    n_kv: int,
    stat_dtype,
    acc_dtype,
    score_dtype,
    out_dtype,
    num: KernelNumerics,
):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_BIG)
        l_scr[...] = jnp.zeros_like(l_scr)
        f_scr[...] = jnp.zeros_like(f_scr)
        cnt_scr[0, 0] = 0
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Causal block skipping: block (i, j) is dead iff its first row cannot see
    # its first column, i.e. i*bq + bq - 1 < j*bkv  <=>  all rows below diag.
    if causal:
        live = (i + 1) * block_q - 1 >= j * block_kv
    else:
        live = True

    @pl.when(live if causal else j >= 0)
    def _step():
        q = q_ref[0]          # (bq, d)
        k = k_ref[0]          # (bkv, d)  (already PASA-shifted + scaled)
        v = v_ref[0]          # (bkv, d)

        def fit_stat(x):
            return num.fit(x, stat_dtype)

        s = num.fit(jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=num.precision,
        ), score_dtype)                            # (bq, bkv)
        if post_scale != 1.0:
            s = num.fit(s * num.const(post_scale, score_dtype), score_dtype)

        # Row pseudo-average of the full (unmasked) block - Eq. 14 requires
        # the mean over exactly the columns the shift used.  Reductions
        # accumulate wide and round once on the store (see
        # repro.core.precision.reduce_dtype), as ones-vector dot_general
        # contractions with shape-fixed accumulation order (same rationale
        # as pasa_decode.masked_block_update).
        wide = reduce_dtype(stat_dtype)
        ones = jnp.ones((block_kv, 1), wide)
        sbar = fit_stat(
            jax.lax.dot_general(
                s.astype(wide), ones, (((1,), (0,)), ((), ())),
                preferred_element_type=wide, precision=num.precision,
            ) / block_kv
        )

        if causal:
            rows = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0
            )
            cols = j * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1
            )
            s = jnp.where(rows >= cols, s, jnp.asarray(NEG_BIG, s.dtype))

        s_stat = fit_stat(s)
        m_loc = jnp.max(
            s_stat.astype(num.storage(stat_dtype)), axis=-1, keepdims=True
        )
        p = num.fit(jnp.exp(fit_stat(s_stat - m_loc)), score_dtype)
        l_loc = fit_stat(jax.lax.dot_general(
            p.astype(wide), ones, (((1,), (0,)), ((), ())),
            preferred_element_type=wide, precision=num.precision,
        ))

        cnt = cnt_scr[0, 0]
        m_new, l_new, f_new, e_prev, e_cur = online_update(
            m_scr[:, :1], l_scr[:, :1], f_scr[:, :1], cnt, m_loc, l_loc,
            sbar, inva=inva, stat_dtype=stat_dtype, num=num,
        )
        if inva != 0.0:
            f_scr[...] = jnp.broadcast_to(f_new, f_scr.shape)

        pv = num.fit(jax.lax.dot_general(
            p, num.fit(v, score_dtype), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=num.precision,
        ), acc_dtype)
        acc_scr[...] = accumulate(
            acc_scr[...], e_prev, e_cur, pv, acc_dtype=acc_dtype, num=num
        )
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)
        cnt_scr[0, 0] = cnt + 1

    @pl.when(j == n_kv - 1)
    def _fin():
        l = num.fit(l_scr[:, :1], acc_dtype)
        o_ref[0] = num.fit(acc_scr[...] / l, out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "inva", "post_scale", "causal", "block_q", "block_kv",
        "stat_dtype", "acc_dtype", "score_dtype", "out_dtype", "interpret",
    ),
)
def attention_kernel_call(
    q: jnp.ndarray,            # (B, H, S1, D)
    k_shifted: jnp.ndarray,    # (B, KVH, S2, D) - pre-shifted (or pre-scaled)
    v: jnp.ndarray,            # (B, KVH, S2, D)
    *,
    inva: float,
    post_scale: float = 1.0,
    causal: bool = False,
    block_q: int = 128,
    block_kv: int = 128,
    stat_dtype=jnp.float32,
    acc_dtype=jnp.float32,
    score_dtype=jnp.float16,
    out_dtype=jnp.float16,
    interpret: bool = False,
) -> jnp.ndarray:
    b, h, s1, d = q.shape
    _, kvh, s2, _ = k_shifted.shape
    if h % kvh:
        raise ValueError(f"q heads {h} not a multiple of kv heads {kvh}")
    group = h // kvh
    if s1 % block_q or s2 % block_kv:
        raise ValueError(
            f"S1={s1} %% block_q={block_q} and S2={s2} %% block_kv={block_kv}"
            " must be 0 (ops.py pads)"
        )
    n_q, n_kv = s1 // block_q, s2 // block_kv

    num = KernelNumerics(emulate_f16=not interpret)
    qr = num.to_storage(q.reshape(b * h, s1, d))

    def q_map(bh, i, j):
        return (bh, i, 0)

    def kv_map(bh, i, j):
        bb = bh // h
        kh = (bh % h) // group
        return (bb * kvh + kh, j, 0)

    kr = num.to_storage(k_shifted.reshape(b * kvh, s2, d))
    vr = num.to_storage(v.reshape(b * kvh, s2, d))

    kernel = functools.partial(
        _attn_kernel,
        inva=inva, post_scale=post_scale, causal=causal,
        block_q=block_q, block_kv=block_kv, n_kv=n_kv,
        stat_dtype=stat_dtype, acc_dtype=acc_dtype, score_dtype=score_dtype,
        out_dtype=out_dtype, num=num,
    )
    stat = num.storage(stat_dtype)

    out = pl.pallas_call(
        kernel,
        grid=(b * h, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, block_kv, d), kv_map),
            pl.BlockSpec((1, block_kv, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), q_map),
        out_shape=jax.ShapeDtypeStruct(
            (b * h, s1, d), num.storage(out_dtype)
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), stat),   # m
            pltpu.VMEM((block_q, _LANES), stat),   # l
            pltpu.VMEM((block_q, _LANES), stat),   # f (global pseudo-avg)
            pltpu.SMEM((1, 1), jnp.int32),         # processed-block count
            pltpu.VMEM((block_q, d), num.storage(acc_dtype)),  # accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, h, s1, d).astype(out_dtype)
