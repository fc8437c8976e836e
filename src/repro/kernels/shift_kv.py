"""Batched-GEMM K pre-processing kernel (Algorithm 1 lines 5-7).

Applies ``K'_j = M K_j`` per KV block (M symmetric), computing the
pseudo-average subtraction + static scaling as one MXU pass - the paper's
"matrix-naive method to tackle the bias subtraction on matrix engines".

Grid: (B*KVH, Nkv).  M is a single (block_kv, block_kv) VMEM-resident tile
shared by every cell (index_map pins it to (0, 0)).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fp16 import KernelNumerics


def _shift_kernel(m_ref, k_ref, o_ref, *, out_dtype, num: KernelNumerics):
    k = k_ref[0]                      # (bkv, d)
    m = m_ref[...]                    # (bkv, bkv)
    o_ref[0] = num.fit(jax.lax.dot_general(
        m, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        precision=num.precision,
    ), out_dtype)


@functools.partial(
    jax.jit, static_argnames=("block_kv", "out_dtype", "interpret")
)
def shift_kv_kernel_call(
    m: jnp.ndarray,     # (block_kv, block_kv) shifting matrix, low precision
    k: jnp.ndarray,     # (B, KVH, S2, D)
    *,
    block_kv: int = 128,
    out_dtype=jnp.float16,
    interpret: bool = False,
) -> jnp.ndarray:
    b, kvh, s2, d = k.shape
    if s2 % block_kv:
        raise ValueError(f"S2={s2} not divisible by block_kv={block_kv}")
    n_kv = s2 // block_kv
    num = KernelNumerics(emulate_f16=not interpret)
    kr = num.to_storage(k.reshape(b * kvh, s2, d))

    out = pl.pallas_call(
        functools.partial(_shift_kernel, out_dtype=out_dtype, num=num),
        grid=(b * kvh, n_kv),
        in_specs=[
            pl.BlockSpec((block_kv, block_kv), lambda bh, j: (0, 0)),
            pl.BlockSpec((1, block_kv, d), lambda bh, j: (bh, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_kv, d), lambda bh, j: (bh, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b * kvh, s2, d), num.storage(out_dtype)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )(num.to_storage(m), kr)
    return out.reshape(b, kvh, s2, d).astype(out_dtype)
