"""Ring (sequence-parallel) PASA over a mesh axis.

The paper notes PASA "is able to be integrated into ... recently developed
distributed version - ring attention (RA) for multiple devices".  This module
realizes that claim: KV shards rotate around a ring (lax.ppermute) while each
device folds the visiting shard into its local PASA state with the *same*
``update_state`` as the single-device path - the global pseudo-average F-bar
update is a weighted running mean, so it composes across devices in ring order
exactly as it does across blocks.

Communication/compute overlap: each ring step's ppermute of the *next* KV
shard is issued before the current shard's block-scan, so the ICI transfer
hides behind the O(S1 * s2 * D) block compute (the standard RA schedule).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import pasa as pasa_lib
from repro.core.precision import FP16, PrecisionPolicy
from repro.core.shifting import (
    effective_invariance,
    shift_kv_blocks,
    shifting_matrix,
)


def ring_pasa_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    axis_name: str,
    beta: float = 0.0,
    policy: PrecisionPolicy = FP16,
    block_kv: int = 128,
    causal: bool = False,
    kv_len: Optional[jnp.ndarray] = None,
    q_offset: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Sequence-parallel blocked attention inside shard_map.

    Args:
      q: (..., S1_local, D) local query shard.
      k, v: (..., S2_local, D) local KV shard (S2_local % block_kv == 0).
      axis_name: mesh axis the sequence is sharded over.
      causal: causal over *global* positions; shard r owns rows
        [r*S1_local, (r+1)*S1_local) and cols [r*S2_local, ...).
      kv_len: optional per-batch valid GLOBAL column count (columns at or
        beyond it are masked out on every device) - the ragged-tail
        convention of the paged serving stack, where gathered pages run
        past the live sequence.  Shape: broadcastable against the lead
        dims of q/k with trailing (S1, s2) added, e.g. ``(B, 1, 1, 1)``
        for (B, H, S, D) inputs (callers with a flat (B,) pass
        ``kv_len[:, None, None, None]``).  Masked columns are excluded
        from both the softmax and the ring blocks' pseudo-averages: K/V
        garbage past kv_len must be zeroed by the caller so the GEMM-form
        shift stays finite (the recovery identity holds for any shift
        vector, so the zeros only alter rounding, not the exact softmax).
      q_offset: optional per-batch GLOBAL row offset of the local query
        shard's row 0 (same broadcast contract, trailing (S1, s2)); used
        with ``causal=True`` when the query block sits at a dynamic
        position - the chunked-prefill case.

    Must be called under shard_map with q/k/v sharded on the seq dim of
    ``axis_name`` and replicated output semantics handled by the caller.
    """
    if not 0.0 <= beta < 1.0:
        raise ValueError(f"beta must be in [0,1), got {beta}")
    d = q.shape[-1]
    s1 = q.shape[-2]
    s2_loc = k.shape[-2]
    if s2_loc % block_kv:
        raise ValueError(f"local KV len {s2_loc} % block_kv {block_kv} != 0")
    n_dev = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)

    q = q.astype(policy.input_dtype)
    k = k.astype(policy.input_dtype)
    v = v.astype(policy.input_dtype)

    post_scale = 1.0
    if beta > 0.0:
        inva = effective_invariance(block_kv, d, beta, policy.input_dtype)
        m_mat = shifting_matrix(block_kv, d, beta, dtype=policy.input_dtype)
        k = shift_kv_blocks(k, m_mat, block_kv).astype(policy.input_dtype)
    else:
        inva = 0.0
        post_scale = 1.0 / float(np.sqrt(d))

    lead = jnp.broadcast_shapes(q.shape[:-2], k.shape[:-2])
    qs = jnp.broadcast_to(q, lead + q.shape[-2:])
    state = pasa_lib.init_state(qs.shape[:-1], d, policy)
    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]

    masked = causal or kv_len is not None
    q_rows = None
    if masked:
        q_rows = jnp.arange(s1, dtype=jnp.int32) + my * s1
        if q_offset is not None:
            q_rows = q_offset + q_rows[:, None]        # (..., S1, 1)
        else:
            q_rows = q_rows[:, None]

    def ring_step(step, carry):
        state, k_cur, v_cur = carry
        # Prefetch the next shard first so the ppermute overlaps the sweep.
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        step = step.astype(jnp.int32)
        src = jax.lax.rem(
            my.astype(jnp.int32) - step + jnp.int32(n_dev), jnp.int32(n_dev)
        )  # owner of k_cur
        state_new = _ring_sweep(
            state, qs, k_cur, v_cur, inva=inva, policy=policy,
            block_kv=block_kv, post_scale=post_scale,
            q_rows=q_rows if masked else None,
            col_base=src * s2_loc if masked else None,
            causal=causal, kv_len=kv_len,
        )
        return (state_new, k_nxt, v_nxt)

    state, _, _ = jax.lax.fori_loop(0, n_dev, ring_step, (state, k, v))
    return pasa_lib.finalize_state(state, policy)


def _ring_sweep(state, q, k_sh, v, *, inva, policy, block_kv, post_scale,
                q_rows, col_base, causal=True, kv_len=None):
    d = q.shape[-1]
    n_blocks = k_sh.shape[-2] // block_kv
    kb = jnp.moveaxis(k_sh.reshape(*k_sh.shape[:-2], n_blocks, block_kv, d), -3, 0)
    vb = jnp.moveaxis(v.reshape(*v.shape[:-2], n_blocks, block_kv, d), -3, 0)
    idx = jnp.arange(n_blocks, dtype=jnp.int32)

    def body(st, inp):
        kj, vj, j = inp
        mask = None
        if q_rows is not None:
            cols = col_base + j * block_kv + jnp.arange(block_kv, dtype=jnp.int32)
            if causal:
                mask = q_rows >= cols[None, :]
            if kv_len is not None:
                valid = cols[None, :] < kv_len
                mask = valid if mask is None else jnp.logical_and(mask, valid)
        st = pasa_lib.update_state(
            st, q, kj, vj, inva=inva, policy=policy, mask=mask,
            post_scale=post_scale,
        )
        return st, None

    state, _ = jax.lax.scan(body, state, (kb, vb, idx))
    return state


def make_ring_attention(mesh, axis_name: str, **kw):
    """Wrap ring_pasa_attention in shard_map for (B, H, S, D) inputs sharded
    on S over ``axis_name`` (other dims replicated or sharded elsewhere by
    the caller's enclosing jit)."""
    from jax.sharding import PartitionSpec as P

    spec = P(None, None, axis_name, None)

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=spec, check_vma=False,
    )
    def fn(q, k, v):
        return ring_pasa_attention(q, k, v, axis_name=axis_name, **kw)

    return fn
