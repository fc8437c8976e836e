"""The PASA shifting matrix (paper Eq. 10) and Theorem 2.1.

``M = (I - (beta/s2) J) / sqrt(d)`` applied on the right of ``K_j^T`` subtracts
``beta x`` the per-block key mean *and* folds in the static ``1/sqrt(d)``
scaling, all as one matrix-engine (MXU / CUBE) pass:

    K'_j^T = K_j^T M  =  (K_j^T - beta * mean_s2(K_j)^T) / sqrt(d)

Theorem 2.1: for ``M = I - lambda J`` (s x s), ``M^-1 = I + lambda/(1-lambda s) J``
iff ``lambda != 1/s`` (for PASA, ``lambda = beta/s2`` so invertibility iff
``beta != 1``).  The inverse is what lets the recovery step reconstruct the
original block row-means from the shifted ones (Eq. 14):

    mean(S'_ij) / (1 - beta)  =  mean(S_ij)        (per row)
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.precision import operand_precision


def shifting_matrix(s2: int, d: int, beta: float, dtype=jnp.float16) -> jnp.ndarray:
    """Build M in ``dtype`` exactly as the paper stores it (fp16 on-chip).

    The fp16 rounding of the two distinct entries of M is the entire subject of
    the optimal-accuracy condition (Appendix A/B): ``beta.py`` solves for the
    ``beta`` whose *rounded* matrix realizes an exactly-representable
    invariance.
    """
    if not (0.0 <= beta < 1.0 or beta == 0.0):
        if beta >= 1.0:
            raise ValueError(f"beta must be < 1 for M to be invertible, got {beta}")
    alpha = float(np.sqrt(d))
    diag = np.float64((1.0 - beta / s2) / alpha)
    off = np.float64((-beta / s2) / alpha)
    m = np.full((s2, s2), off, np.float64)
    np.fill_diagonal(m, diag)
    return jnp.asarray(m).astype(dtype)


def effective_invariance(s2: int, d: int, beta: float, dtype=jnp.float16) -> float:
    """The invariance realized by the *stored* M, including the alpha fold-in.

    After rounding, M = a I - b J (entrywise in ``dtype``).  For scores
    T = a*S (the intended statically-scaled scores, with ``a ~= 1/sqrt(d)``),
    the shift M actually subtracted per row is ``bn/(a - bn)`` times the row
    mean of the *shifted* block - this is the multiplier the recovery step
    must use (Appendix A/B generalized to the alpha-folded matrix; at exact
    arithmetic it reduces to beta/(1-beta)).
    """
    n = s2
    alpha = np.float64(np.sqrt(d))
    if dtype == jnp.float64 or dtype == jnp.float32:
        return float(beta / (1.0 - beta))
    cast = np.float16 if dtype == jnp.float16 else None
    if cast is None:  # bfloat16: round via jnp
        diag = float(jnp.asarray((1.0 - beta / n) / alpha, jnp.bfloat16))
        off = float(jnp.asarray((-beta / n) / alpha, jnp.bfloat16))
    else:
        diag = float(np.float64(cast((1.0 - beta / n) / alpha)))
        off = float(np.float64(cast((-beta / n) / alpha)))
    b = -off
    a = diag + b
    return float(b * n / (a - b * n))


def shifting_matrix_inverse(s2: int, d: int, beta: float, dtype=jnp.float64) -> jnp.ndarray:
    """Closed-form inverse of the *unscaled* core from Theorem 2.1, times alpha.

    M = (I - lam J)/alpha with lam = beta/s2  =>  M^-1 = alpha (I + lam/(1-lam s2) J).
    """
    if beta == 1.0:
        raise ValueError("M is singular at beta == 1 (Theorem 2.1)")
    lam = beta / s2
    alpha = float(np.sqrt(d))
    eye = jnp.eye(s2, dtype=dtype)
    ones = jnp.ones((s2, s2), dtype=dtype)
    return alpha * (eye + (lam / (1.0 - lam * s2)) * ones)


def shift_kv_blocks(k: jnp.ndarray, m: jnp.ndarray, block_kv: int) -> jnp.ndarray:
    """Paper Algorithm 1 lines 5-7: batched-GEMM pre-processing of K.

    Applies ``K'_j^T = K_j^T M`` per KV block.  Because M is symmetric this is
    ``K'_j = M K_j`` - implemented as one einsum over the blocked view so XLA
    emits a single batched GEMM (the paper's "matrix-naive method... on matrix
    engines").

    The contraction accumulates one precision level wider than ``m``'s
    storage dtype and rounds ONCE on the store — matrix-engine (MXU / CUBE)
    semantics, and exactly what kernels/shift_kv.py does
    (``preferred_element_type=float32``).  Accumulating at the fp16 operand
    dtype instead is NOT reproducible: XLA's low-precision reduction order
    depends on the operand layout, so the same key block shifted inside a
    (B, KVH, ...) tensor vs a GQA-expanded (B, H, ...) tensor rounds
    differently (observed up to 5e-2 per element on resonance inputs) —
    which is the "Is Flash Attention Stable?" implementation-divergence
    failure mode this reference exists to catch, not exhibit.

    Args:
      k: (..., S2, D) keys, S2 % block_kv == 0 (pad first; see pasa.py).
      m: (block_kv, block_kv) shifting matrix.
      block_kv: block size s2.

    Returns:
      (..., S2, D) shifted+scaled keys, in ``m``'s dtype (single rounding
      from the wide accumulator).
    """
    *lead, s2, dd = k.shape
    if s2 % block_kv:
        raise ValueError(f"S2={s2} not divisible by block_kv={block_kv}")
    kb = k.reshape(*lead, s2 // block_kv, block_kv, dd)
    acc_t = jnp.float64 if m.dtype == jnp.float64 else jnp.float32
    out = jnp.einsum(
        "st,...jtd->...jsd", m, kb.astype(m.dtype),
        preferred_element_type=acc_t, precision=operand_precision(m.dtype),
    ).astype(m.dtype)
    return out.reshape(*lead, s2, dd)


def shift_kv_reference(k: jnp.ndarray, d: int, beta: float, block_kv: int) -> jnp.ndarray:
    """Algebraic oracle for shift_kv_blocks: (K - beta*blockmean(K)) / sqrt(d).

    Computed in fp64 - used only in tests to validate the GEMM formulation.
    """
    *lead, s2, dd = k.shape
    kb = k.astype(jnp.float64).reshape(*lead, s2 // block_kv, block_kv, dd)
    mean = kb.mean(axis=-2, keepdims=True)
    out = (kb - beta * mean) / np.sqrt(d)
    return out.reshape(*lead, s2, dd)
