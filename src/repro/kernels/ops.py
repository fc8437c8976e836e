"""Public, jit'd entry points for the Pallas kernels.

These wrappers own everything the raw kernels don't: precision-policy plumbing
(the effective invariance of the rounded shifting matrix), the two-pass
pipeline (shift-KV batched GEMM, then the fused attention sweep - Algorithm 1
lines 5-7 then 8-23), GQA head-count checks, and the interpret switch used to
validate on CPU.

On a CPU backend ``interpret=True`` is mandatory (Pallas TPU kernels cannot
lower to host HLO); models therefore route through repro.core's pure-JAX path
unless ``attention_impl = "pallas"`` is selected on a TPU runtime.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import beta as beta_lib
from repro.core import shifting
from repro.core.precision import FP16, PrecisionPolicy

from repro.kernels import pasa_attention as _attn
from repro.kernels import pasa_decode as _decode
from repro.kernels import pasa_paged_decode as _paged
from repro.kernels import pasa_paged_prefill as _paged_prefill
from repro.kernels import shift_kv as _shift


def _check(q, k, v):
    if q.ndim != 4 or k.ndim != 4 or v.ndim != 4:
        raise ValueError("expected (B, H, S, D) tensors")
    if k.shape != v.shape:
        raise ValueError(f"k/v shape mismatch: {k.shape} vs {v.shape}")
    if q.shape[0] != k.shape[0] or q.shape[-1] != k.shape[-1]:
        raise ValueError(f"q {q.shape} incompatible with kv {k.shape}")
    if q.shape[1] % k.shape[1]:
        raise ValueError(f"q heads {q.shape[1]} % kv heads {k.shape[1]} != 0")


def pasa_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    beta: float = beta_lib.DEFAULT_BETA,
    policy: PrecisionPolicy = FP16,
    block_q: int = 128,
    block_kv: int = 128,
    causal: bool = False,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused PASA attention: shift-KV GEMM pass + online-recovery sweep.

    q: (B, H, S1, D); k, v: (B, KVH, S2, D).  S1 % block_q == 0,
    S2 % block_kv == 0 (kernels are the aligned fast path; ragged shapes go
    through repro.core.blocked_attention).
    """
    _check(q, k, v)
    d = q.shape[-1]
    q = q.astype(policy.input_dtype)
    k = k.astype(policy.input_dtype)
    v = v.astype(policy.input_dtype)

    if beta > 0.0:
        m = shifting.shifting_matrix(block_kv, d, beta, dtype=policy.input_dtype)
        k_sh = _shift.shift_kv_kernel_call(
            m, k, block_kv=block_kv, out_dtype=policy.input_dtype,
            interpret=interpret,
        )
        inva = shifting.effective_invariance(block_kv, d, beta, policy.input_dtype)
        post_scale = 1.0
    else:
        k_sh = k
        inva = 0.0
        post_scale = 1.0 / float(d) ** 0.5

    return _attn.attention_kernel_call(
        q, k_sh, v,
        inva=inva, post_scale=post_scale, causal=causal,
        block_q=block_q, block_kv=block_kv,
        stat_dtype=policy.stat_dtype, acc_dtype=policy.acc_dtype,
        score_dtype=policy.score_dtype, out_dtype=policy.out_dtype,
        interpret=interpret,
    )


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    policy: PrecisionPolicy = FP16,
    block_q: int = 128,
    block_kv: int = 128,
    causal: bool = False,
    interpret: bool = False,
) -> jnp.ndarray:
    """FlashAttention-2 baseline kernel (identical tiling, no PASA steps)."""
    return pasa_attention(
        q, k, v, beta=0.0, policy=policy, block_q=block_q, block_kv=block_kv,
        causal=causal, interpret=interpret,
    )


def pasa_decode(
    q: jnp.ndarray,        # (B, KVH, G, D) grouped query heads, one token
    k_cache: jnp.ndarray,  # (B, KVH, S2, D) zero-padded raw cache
    v_cache: jnp.ndarray,
    kv_len: jnp.ndarray,   # (B,)
    *,
    beta: float = beta_lib.DEFAULT_BETA,
    policy: PrecisionPolicy = FP16,
    block_kv: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """GQA flash-decode with inline algebraic PASA shifting.

    The algebraic (masked-block-mean) shift uses the exact beta, so the ideal
    invariance beta/(1-beta) is the correct recovery multiplier here (the
    rounded-matrix correction of Appendix A applies only to the GEMM form).
    """
    if q.ndim != 4:
        raise ValueError("q must be (B, KVH, G, D)")
    inva = beta / (1.0 - beta) if beta > 0.0 else 0.0
    return _decode.decode_kernel_call(
        q.astype(policy.input_dtype),
        k_cache.astype(policy.input_dtype),
        v_cache.astype(policy.input_dtype),
        kv_len,
        inva=inva, beta=beta, block_kv=block_kv,
        stat_dtype=policy.stat_dtype, acc_dtype=policy.acc_dtype,
        score_dtype=policy.score_dtype, out_dtype=policy.out_dtype,
        interpret=interpret,
    )


def _check_quant(k_pages, quant):
    """Validate the all-or-none sidecar bundle; returns the kwargs dict."""
    names = ("k_scale", "k_shift", "v_scale", "v_shift")
    given = [q is not None for q in quant]
    if not any(given):
        return {}
    if not all(given):
        raise ValueError(f"quantized pool needs all of {names}")
    p, _, kvh, d = k_pages.shape
    for name, arr, want in zip(
        names, quant,
        ((p, kvh), (p, kvh, d), (p, kvh), (p, kvh, d)),
    ):
        if tuple(arr.shape) != want:
            raise ValueError(f"{name} shape {arr.shape} != {want}")
    return dict(zip(names, quant))


def pasa_paged_decode(
    q: jnp.ndarray,          # (B, KVH, G, D) grouped query heads, one token
    k_pages: jnp.ndarray,    # (num_pages, page, KVH, D) raw physical pages,
    v_pages: jnp.ndarray,    #   or fp8/int8 codes when sidecars are given
    page_table: jnp.ndarray, # (B, max_pages) int32
    kv_len: jnp.ndarray,     # (B,)
    *,
    beta: float = beta_lib.DEFAULT_BETA,
    policy: PrecisionPolicy = FP16,
    k_scale: Optional[jnp.ndarray] = None,   # (P, KVH) f32
    k_shift: Optional[jnp.ndarray] = None,   # (P, KVH, D) f32
    v_scale: Optional[jnp.ndarray] = None,
    v_shift: Optional[jnp.ndarray] = None,
    interpret: bool = False,
    use_kernel: bool = True,
) -> jnp.ndarray:
    """GQA flash-decode over a paged KV cache.

    ``use_kernel=True`` runs the Pallas kernel (page-table scalar prefetch;
    TPU, or CPU via ``interpret=True``); ``use_kernel=False`` takes the XLA
    ``jnp.take`` gather fallback.  Both use the masked valid-column shift
    (``shift_mask_valid`` convention), so page granularity == PASA block
    granularity and recycled pages need no scrubbing.

    Passing the four sidecar arrays selects the quantized-pool mode: pages
    are fp8/int8 shift-centered codes (runtime/paged_cache.py), dequantized
    in VMEM (kernel) / post-gather (XLA fallback) at
    ``policy.input_dtype``.
    """
    if q.ndim != 4:
        raise ValueError("q must be (B, KVH, G, D)")
    if k_pages.ndim != 4 or k_pages.shape != v_pages.shape:
        raise ValueError(
            f"pages must be (P, page, KVH, D); got {k_pages.shape} / "
            f"{v_pages.shape}"
        )
    quant = _check_quant(k_pages, (k_scale, k_shift, v_scale, v_shift))
    if not quant:
        k_pages = k_pages.astype(policy.input_dtype)
        v_pages = v_pages.astype(policy.input_dtype)
    if not use_kernel:
        return _paged.paged_decode_xla(
            q.astype(policy.input_dtype),
            k_pages, v_pages,
            page_table, kv_len,
            beta=beta, policy=policy, block_kv=k_pages.shape[1],
            **quant,
        )
    inva = beta / (1.0 - beta) if beta > 0.0 else 0.0
    return _paged.paged_decode_kernel_call(
        q.astype(policy.input_dtype),
        k_pages, v_pages,
        page_table, kv_len,
        inva=inva, beta=beta,
        stat_dtype=policy.stat_dtype, acc_dtype=policy.acc_dtype,
        score_dtype=policy.score_dtype, out_dtype=policy.out_dtype,
        deq_dtype=policy.input_dtype,
        interpret=interpret,
        **quant,
    )


def pasa_paged_prefill(
    q: jnp.ndarray,          # (B, H, CS, D) chunk queries, full query heads
    k_pages: jnp.ndarray,    # (num_pages, page, KVH, D) raw physical pages,
    v_pages: jnp.ndarray,    #   or fp8/int8 codes when sidecars are given
    page_table: jnp.ndarray, # (B, max_pages) int32
    chunk_start: jnp.ndarray,  # (B,) absolute position of the chunk's row 0
    kv_len: jnp.ndarray,     # (B,) valid KV length (chunk end)
    *,
    beta: float = beta_lib.DEFAULT_BETA,
    policy: PrecisionPolicy = FP16,
    k_scale: Optional[jnp.ndarray] = None,   # (P, KVH) f32
    k_shift: Optional[jnp.ndarray] = None,   # (P, KVH, D) f32
    v_scale: Optional[jnp.ndarray] = None,
    v_shift: Optional[jnp.ndarray] = None,
    block_q: int = 128,
    interpret: bool = False,
    use_kernel: bool = True,
) -> jnp.ndarray:
    """Chunked prefill over a paged KV cache (chunk-exact convention).

    The chunk's K/V must already be scattered into their pages; queries
    attend causally over cached-prefix pages and the in-flight chunk
    through the page table.  The B rows may belong to DIFFERENT requests
    (the serving engine's batched multi-request prefill): each row
    carries its own ``chunk_start``, ``kv_len``, and page-table row, and
    a dead pad row (``kv_len == 0``) emits exact zeros on both paths.
    ``use_kernel=True`` runs the Pallas kernel (page-table scalar
    prefetch; TPU, or CPU via ``interpret=True``); ``use_kernel=False``
    takes the XLA gather fallback.  Both use the chunk-exact shift
    (page-local valid-column mean, causal mask after sbar, per-row
    dead-page no-ops), so outputs are bit-invariant to the chunk
    schedule - the prefix cache's exactness contract.

    Passing the four sidecar arrays selects the quantized-pool mode (see
    :func:`pasa_paged_decode`); quantization params are per page, so the
    dequantized values - and hence the chunk-exact bit-invariance - are
    preserved at fp8/int8.
    """
    if q.ndim != 4:
        raise ValueError("q must be (B, H, CS, D)")
    if k_pages.ndim != 4 or k_pages.shape != v_pages.shape:
        raise ValueError(
            f"pages must be (P, page, KVH, D); got {k_pages.shape} / "
            f"{v_pages.shape}"
        )
    quant = _check_quant(k_pages, (k_scale, k_shift, v_scale, v_shift))
    if not quant:
        k_pages = k_pages.astype(policy.input_dtype)
        v_pages = v_pages.astype(policy.input_dtype)
    if not use_kernel:
        return _paged_prefill.paged_prefill_xla(
            q.astype(policy.input_dtype),
            k_pages, v_pages,
            page_table, chunk_start, kv_len,
            beta=beta, policy=policy,
            **quant,
        )
    inva = beta / (1.0 - beta) if beta > 0.0 else 0.0
    return _paged_prefill.paged_prefill_kernel_call(
        q.astype(policy.input_dtype),
        k_pages, v_pages,
        page_table, chunk_start, kv_len,
        inva=inva, beta=beta, block_q=block_q,
        stat_dtype=policy.stat_dtype, acc_dtype=policy.acc_dtype,
        score_dtype=policy.score_dtype, out_dtype=policy.out_dtype,
        deq_dtype=policy.input_dtype,
        interpret=interpret,
        **quant,
    )


def pasa_paged_verify(
    q: jnp.ndarray,          # (B, KVH, G, W, D) grouped queries, W positions
    k_pages: jnp.ndarray,    # (num_pages, page, KVH, D) raw physical pages,
    v_pages: jnp.ndarray,    #   or fp8/int8 codes when sidecars are given
    page_table: jnp.ndarray, # (B, max_pages) int32
    start: jnp.ndarray,      # (B,) absolute position of query column 0
    *,
    beta: float = beta_lib.DEFAULT_BETA,
    policy: PrecisionPolicy = FP16,
    k_scale: Optional[jnp.ndarray] = None,   # (P, KVH) f32
    k_shift: Optional[jnp.ndarray] = None,   # (P, KVH, D) f32
    v_scale: Optional[jnp.ndarray] = None,
    v_shift: Optional[jnp.ndarray] = None,
    interpret: bool = False,
    use_kernel: bool = True,
) -> jnp.ndarray:
    """Speculative-verify attention: W consecutive decode positions per
    row over a paged KV cache -> (B, KVH, G, W, D).

    Query column j attends exactly as a plain decode at position
    ``start + j`` would - the SAME :func:`pasa_paged_decode` computation
    with ``kv_len = start + 1 + j`` (the j-th draft's K/V must already be
    scattered into its page, as the engine's chained-sub-step verify
    does).  Each column's output is therefore BIT-IDENTICAL to the
    one-token decode path at that position, which is what makes greedy
    draft acceptance bit-exact: the verifier IS the decoder, run W
    times.  Implemented as W decode calls (kernel or XLA fallback per
    ``use_kernel``) - the verify is latency-bound by the engine's
    chained KV appends, not by this attention, so a fused multi-query
    kernel is deliberately left to the TPU-hardware pass
    (ROADMAP "TPU-hardware kernel validation").

    Note the deliberate CONVENTION choice: this uses the decode shift
    (``shift_mask_valid``), NOT the chunk-exact prefill shift - the two
    round differently on interior rows, and bit-exactness against the
    non-speculative stream requires the decode convention (see
    runtime/README.md "Speculative decoding")."""
    if q.ndim != 5:
        raise ValueError("q must be (B, KVH, G, W, D)")
    w = q.shape[3]
    cols = [
        pasa_paged_decode(
            q[:, :, :, j], k_pages, v_pages, page_table,
            start + 1 + j,
            beta=beta, policy=policy,
            k_scale=k_scale, k_shift=k_shift,
            v_scale=v_scale, v_shift=v_shift,
            interpret=interpret, use_kernel=use_kernel,
        )
        for j in range(w)
    ]
    return jnp.stack(cols, axis=3)


# ---------------------------------------------------------------------------
# Model-axis sharded entry points (tensor-parallel paged serving)
# ---------------------------------------------------------------------------
#
# Both paged kernels are PER-KV-HEAD-LOCAL computations: the page shift,
# softmax statistics, and PV contraction never cross the KVH axis.  When
# the mesh's model axis divides the kv heads, the whole call therefore
# splits under shard_map along KVH with zero collectives - each device
# runs the SAME kernel (Pallas on TPU, the XLA gather fallback elsewhere -
# the GSPMD path) on its head shard of the page pool, and the concatenated
# output is BIT-IDENTICAL to the single-device call (asserted on the
# adversarial generators in tests/test_sharded_serving.py).  When the kv
# heads do NOT divide the model axis, the prefill entry falls back to
# core/ring.py ring-PASA: the pool stays replicated and the chunk's query
# rows + gathered KV shard over the model axis sequence-parallel instead.
# The ring fold order depends on the device count, so that path is
# EXACT-softmax but only RMSE-close to the one-device call, not
# bit-identical - which is why the serving engine only shards pools at
# kv-head granularity (runtime/README.md).  Decode has a single query
# token (nothing to sequence-shard), so its non-divisible fallback is the
# plain replicated call.


def _axis_size_of(mesh, axis: str) -> int:
    from repro.runtime.paged_cache import model_axis_size

    return model_axis_size(mesh, axis)


def pasa_paged_decode_sharded(
    q: jnp.ndarray,          # (B, KVH, G, D)
    k_pages: jnp.ndarray,    # (P, page, KVH, D)
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,
    kv_len: jnp.ndarray,
    *,
    mesh,
    axis: str = "model",
    beta: float = beta_lib.DEFAULT_BETA,
    policy: PrecisionPolicy = FP16,
    k_scale: Optional[jnp.ndarray] = None,
    k_shift: Optional[jnp.ndarray] = None,
    v_scale: Optional[jnp.ndarray] = None,
    v_shift: Optional[jnp.ndarray] = None,
    interpret: bool = False,
    use_kernel: bool = True,
) -> jnp.ndarray:
    """:func:`pasa_paged_decode` kv-head-split over ``mesh``'s ``axis``.

    Page table and kv_len replicate; q and the page pool (plus quantized
    sidecars) split on their KVH dims.  Bit-identical to the unsharded
    call when ``KVH % axis_size == 0``; otherwise falls back to the
    replicated single-call path (see the section comment).
    """
    msize = _axis_size_of(mesh, axis)
    kvh = q.shape[1]
    kw = dict(
        beta=beta, policy=policy, interpret=interpret, use_kernel=use_kernel,
        k_scale=k_scale, k_shift=k_shift, v_scale=v_scale, v_shift=v_shift,
    )
    if msize <= 1 or kvh % msize:
        return pasa_paged_decode(q, k_pages, v_pages, page_table, kv_len, **kw)

    from jax.sharding import PartitionSpec as P

    qspec = P(None, axis, None, None)
    pspec = P(None, None, axis, None)
    in_specs = [qspec, pspec, pspec, P(None, None), P(None)]
    args = [q, k_pages, v_pages, page_table, kv_len]
    names = ("k_scale", "k_shift", "v_scale", "v_shift")
    if k_scale is not None:
        in_specs += [P(None, axis), P(None, axis, None)] * 2
        args += [k_scale, k_shift, v_scale, v_shift]

    def local(q_, kp, vp, pt, kl, *quant):
        return pasa_paged_decode(
            q_, kp, vp, pt, kl, beta=beta, policy=policy,
            interpret=interpret, use_kernel=use_kernel,
            **dict(zip(names, quant)),
        )

    fn = jax.shard_map(
        local, mesh=mesh, in_specs=tuple(in_specs), out_specs=qspec,
        check_vma=False,
    )
    return fn(*args)


def pasa_paged_prefill_sharded(
    q: jnp.ndarray,          # (B, H, CS, D)
    k_pages: jnp.ndarray,    # (P, page, KVH, D)
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,
    chunk_start: jnp.ndarray,
    kv_len: jnp.ndarray,
    *,
    mesh,
    axis: str = "model",
    beta: float = beta_lib.DEFAULT_BETA,
    policy: PrecisionPolicy = FP16,
    k_scale: Optional[jnp.ndarray] = None,
    k_shift: Optional[jnp.ndarray] = None,
    v_scale: Optional[jnp.ndarray] = None,
    v_shift: Optional[jnp.ndarray] = None,
    block_q: int = 128,
    interpret: bool = False,
    use_kernel: bool = True,
) -> jnp.ndarray:
    """:func:`pasa_paged_prefill` sharded over ``mesh``'s ``axis``.

    ``KVH % axis_size == 0``: kv-head split (queries split along their
    kv-head-major H axis so each device keeps whole GQA groups) -
    bit-identical to the unsharded call.  Otherwise: the core/ring.py
    ring-PASA sequence-parallel fallback - the replicated pool's pages
    are gathered/dequantized to the contiguous KV view, garbage beyond
    ``kv_len`` is zeroed, and query rows + KV columns ring over the axis
    (exact softmax; NOT bit-identical - the fold order is device-count
    -dependent).  The ring path needs ``CS % axis_size == 0``,
    ``S2 % axis_size == 0`` and a page-aligned local KV shard; anything
    else takes the plain replicated call.
    """
    msize = _axis_size_of(mesh, axis)
    h = q.shape[1]
    kvh = k_pages.shape[2]
    kw = dict(
        beta=beta, policy=policy, block_q=block_q, interpret=interpret,
        use_kernel=use_kernel,
        k_scale=k_scale, k_shift=k_shift, v_scale=v_scale, v_shift=v_shift,
    )
    if msize <= 1:
        return pasa_paged_prefill(
            q, k_pages, v_pages, page_table, chunk_start, kv_len, **kw
        )
    if kvh % msize == 0 and h % msize == 0:
        from jax.sharding import PartitionSpec as P

        qspec = P(None, axis, None, None)
        pspec = P(None, None, axis, None)
        in_specs = [qspec, pspec, pspec, P(None, None), P(None), P(None)]
        args = [q, k_pages, v_pages, page_table, chunk_start, kv_len]
        names = ("k_scale", "k_shift", "v_scale", "v_shift")
        if k_scale is not None:
            in_specs += [P(None, axis), P(None, axis, None)] * 2
            args += [k_scale, k_shift, v_scale, v_shift]

        def local(q_, kp, vp, pt, cs, kl, *quant):
            return pasa_paged_prefill(
                q_, kp, vp, pt, cs, kl, beta=beta, policy=policy,
                block_q=block_q, interpret=interpret, use_kernel=use_kernel,
                **dict(zip(names, quant)),
            )

        fn = jax.shard_map(
            local, mesh=mesh, in_specs=tuple(in_specs), out_specs=qspec,
            check_vma=False,
        )
        return fn(*args)
    return _paged_prefill_ring(
        q, k_pages, v_pages, page_table, chunk_start, kv_len,
        mesh=mesh, axis=axis, msize=msize, beta=beta, policy=policy,
        k_scale=k_scale, k_shift=k_shift, v_scale=v_scale, v_shift=v_shift,
        block_q=block_q, interpret=interpret, use_kernel=use_kernel,
    )


def _paged_prefill_ring(
    q, k_pages, v_pages, page_table, chunk_start, kv_len, *,
    mesh, axis, msize, beta, policy,
    k_scale, k_shift, v_scale, v_shift,
    block_q, interpret, use_kernel,
):
    """Ring-PASA sequence-parallel fallback for the non-kv-head-divisible
    regime: gather the (replicated) pool to the contiguous KV view, zero
    the garbage tail, and ring q-rows/KV-columns over the model axis with
    causal + valid-column masking (core/ring.py grew both masks for this
    path).  Exact softmax; fold order differs from one device, so this is
    the RMSE-class member of the family."""
    from repro.runtime.paged_cache import gather_pages, gather_pages_dequant

    b, h, cs, d = q.shape
    n_p, page, kvh, _ = k_pages.shape
    g = h // kvh
    s2 = page_table.shape[1] * page
    loc = s2 // msize if s2 % msize == 0 else 0
    if cs % msize or not loc or loc % page:
        # ring needs even, page-aligned shards on both sequence axes;
        # anything else takes the plain replicated call at the CALLER'S
        # kernel/interpret settings
        return pasa_paged_prefill(
            q, k_pages, v_pages, page_table, chunk_start, kv_len,
            beta=beta, policy=policy, block_q=block_q,
            interpret=interpret, use_kernel=use_kernel,
            k_scale=k_scale, k_shift=k_shift, v_scale=v_scale,
            v_shift=v_shift,
        )
    kp2 = k_pages.reshape(n_p, page, kvh * d)
    vp2 = v_pages.reshape(n_p, page, kvh * d)
    if k_scale is not None:
        kseq = gather_pages_dequant(
            kp2, k_scale, k_shift.reshape(n_p, kvh * d), page_table
        )
        vseq = gather_pages_dequant(
            vp2, v_scale, v_shift.reshape(n_p, kvh * d), page_table
        )
    else:
        kseq = gather_pages(kp2.astype(policy.input_dtype), page_table)
        vseq = gather_pages(vp2.astype(policy.input_dtype), page_table)
    # (B, S2, KVH*D) -> (B, KVH, 1, S2, D); zero the invalid tail so the
    # ring's GEMM-form block shift cannot fold stale Inf/NaN debris
    valid = (
        jnp.arange(s2, dtype=jnp.int32)[None, :] < kv_len[:, None]
    )[:, None, None, :, None]
    k5 = jnp.where(
        valid, jnp.moveaxis(kseq.reshape(b, s2, kvh, d), 1, 2)[:, :, None], 0.0
    )
    v5 = jnp.where(
        valid, jnp.moveaxis(vseq.reshape(b, s2, kvh, d), 1, 2)[:, :, None], 0.0
    )
    q5 = q.reshape(b, kvh, g, cs, d)
    roff = chunk_start.astype(jnp.int32).reshape(b, 1, 1, 1, 1)
    klen = kv_len.astype(jnp.int32).reshape(b, 1, 1, 1, 1)

    from jax.sharding import PartitionSpec as P

    from repro.core.ring import ring_pasa_attention

    seq_spec = P(None, None, None, axis, None)
    rep = P(None, None, None, None, None)

    def local(q_, k_, v_, ro, kl):
        return ring_pasa_attention(
            q_, k_, v_, axis_name=axis, beta=beta, policy=policy,
            block_kv=page, causal=True, kv_len=kl, q_offset=ro,
        )

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(seq_spec, seq_spec, seq_spec, rep, rep),
        out_specs=seq_spec, check_vma=False,
    )
    out = fn(q5, k5, v5, roff, klen)
    return out.reshape(b, h, cs, d)


def shift_kv(
    k: jnp.ndarray,
    *,
    beta: float = beta_lib.DEFAULT_BETA,
    block_kv: int = 128,
    policy: PrecisionPolicy = FP16,
    interpret: bool = False,
) -> jnp.ndarray:
    """Standalone K pre-processing (Algorithm 1 lines 5-7) as a kernel call."""
    d = k.shape[-1]
    m = shifting.shifting_matrix(block_kv, d, beta, dtype=policy.input_dtype)
    return _shift.shift_kv_kernel_call(
        m, k.astype(policy.input_dtype), block_kv=block_kv,
        out_dtype=policy.input_dtype, interpret=interpret,
    )
