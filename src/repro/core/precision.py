"""Precision-allocation policies for blocked attention (paper Figures 1-3).

The paper studies three allocations of compute/storage precision inside
FlashAttention:

  * ``FP32``      - original FA: matrix-engine inputs are fp16/bf16 but the
                    score matrix, softmax statistics and output accumulator are
                    fp32 (Figure 1).  Numerically safe, memory-bound on NPU/TPU.
  * ``FP16_FP32`` - partially low precision: the score matrix S leaving the
                    matrix engine is stored fp16; softmax statistics stay fp32
                    (Figure 2).  This is where overflow first appears.
  * ``FP16``      - fully low precision: every intermediate (S, m, l, O-acc)
                    is fp16 (Figure 3).  Highest throughput / lowest data
                    movement; unusable without PASA.

A policy is a small frozen dataclass threaded through every attention
implementation (pure-JAX reference, Pallas kernels, models).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

DType = Any


@dataclasses.dataclass(frozen=True)
class PrecisionPolicy:
    """Where each intermediate of blocked attention lives.

    Attributes:
      name: human-readable policy id.
      input_dtype: dtype Q/K/V are cast to before the matrix engine.
      score_dtype: dtype of the score matrix S as it leaves the first GEMM
        (the matrix engine accumulates wider internally; the *store* is what
        overflows - matching NPU CUBE / TPU MXU semantics).
      stat_dtype: dtype of softmax statistics (running max m, sum l, global
        pseudo-average F).
      acc_dtype: dtype of the output accumulator O.
      out_dtype: dtype of the returned attention output.
    """

    name: str
    input_dtype: DType
    score_dtype: DType
    stat_dtype: DType
    acc_dtype: DType
    out_dtype: DType

    @property
    def overflow_bound(self) -> float:
        """Largest finite value representable by ``score_dtype``."""
        return float(jnp.finfo(self.score_dtype).max)


def reduce_dtype(stat_dtype: DType) -> DType:
    """Wide accumulator dtype for vector-unit reductions (sums / means).

    Reductions feeding cross-block state (block key mean, row pseudo-average
    s-bar, softmax sum l) accumulate one level wider than the policy's
    ``stat_dtype`` store and round ONCE on the store.  This mirrors
    matrix-engine semantics (the MXU / CUBE already accumulates its GEMMs at
    fp32 regardless of operand dtype) and is the reproducibility requirement
    of "Is Flash Attention Stable?" (arXiv:2405.02803): a sum *accumulated*
    at fp16 is not a deterministic function of its inputs across
    implementations - XLA's low-precision reduction order changes with
    operand layout and fusion context, so the same block summed inside a
    Pallas kernel, an eager op, and a fused jit region rounds differently
    (observed: up to 5e-2/element on the shift GEMM across layouts, 3e-3 on
    decode outputs across lowering modes).  A wide accumulate with a single
    narrow store is order-insensitive at any realistic block width, which is
    what lets the kernels and the pure-jnp references agree to
    rounding-level tolerances on every shape.  The *stored* statistics
    (m, l, F-bar, scores, accumulator) keep the policy's dtypes - the
    paper's precision-allocation story (e.g. overflow at the fp16 score
    store) is untouched.  ``max`` reductions are exact and order-free and
    stay at ``stat_dtype``.
    """
    return jnp.float64 if stat_dtype == jnp.float64 else jnp.float32


def operand_precision(dtype):
    """Matmul precision for operands stored at ``dtype``.

    fp16 operands get ``HIGHEST``.  A TPU v5e matrix unit multiplies bf16,
    and at XLA's DEFAULT precision fp16 operands are rounded to bf16 (8 of
    fp16's 11 significant bits) - measured on the chip as a 6x larger PASA
    error on sequence-biased keys.  The paper's fp16 matrix engine
    multiplies fp16 exactly and accumulates wide; HIGHEST reproduces that
    with several bf16 passes.  Every other dtype keeps the default (bf16
    operands are exact in one pass).  CPU backends ignore the setting.
    """
    return jax.lax.Precision.HIGHEST if dtype == jnp.float16 else None


FP32 = PrecisionPolicy(
    name="fp32",
    input_dtype=jnp.float16,
    score_dtype=jnp.float32,
    stat_dtype=jnp.float32,
    acc_dtype=jnp.float32,
    out_dtype=jnp.float16,
)

FP16_FP32 = PrecisionPolicy(
    name="fp16_fp32",
    input_dtype=jnp.float16,
    score_dtype=jnp.float16,
    stat_dtype=jnp.float32,
    acc_dtype=jnp.float32,
    out_dtype=jnp.float16,
)

FP16 = PrecisionPolicy(
    name="fp16",
    input_dtype=jnp.float16,
    score_dtype=jnp.float16,
    stat_dtype=jnp.float16,
    acc_dtype=jnp.float16,
    out_dtype=jnp.float16,
)

# bf16 variant used by the surrounding training framework (TPU-native).  The
# paper notes bf16 inputs should be converted to fp16 inside PASA for optimal
# accuracy; this policy keeps bf16 end-to-end for the *non*-PASA fast path.
BF16_FP32 = PrecisionPolicy(
    name="bf16_fp32",
    input_dtype=jnp.bfloat16,
    score_dtype=jnp.float32,
    stat_dtype=jnp.float32,
    acc_dtype=jnp.float32,
    out_dtype=jnp.bfloat16,
)

# Exactness oracle (tests only).
F64 = PrecisionPolicy(
    name="f64",
    input_dtype=jnp.float64,
    score_dtype=jnp.float64,
    stat_dtype=jnp.float64,
    acc_dtype=jnp.float64,
    out_dtype=jnp.float64,
)

POLICIES = {p.name: p for p in (FP32, FP16_FP32, FP16, BF16_FP32, F64)}


def get_policy(name: str) -> PrecisionPolicy:
    try:
        return POLICIES[name]
    except KeyError as e:
        raise ValueError(
            f"unknown precision policy {name!r}; have {sorted(POLICIES)}"
        ) from e
