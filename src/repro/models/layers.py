"""Shared building blocks: norms, RoPE, SwiGLU, embeddings, chunked CE.

All modules are functional: ``init_*`` builds a pytree of arrays (pure shapes,
safe under jax.eval_shape for the dry-run), ``apply`` is a plain function.
Sharding constraints go through launch.sharding.shard (no-op without a mesh).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.sharding import dp_axes, shard


def _init(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def row_parallel_matmul(x: jnp.ndarray, w: jnp.ndarray, compute_dtype):
    """y = x @ w with the contraction dim sharded over "model".

    XLA's excess-precision pass promotes the partial-sum all-reduce of a
    bf16 row-parallel matmul to f32 (measured; EXPERIMENTS.md Perf
    iteration 4), doubling the dominant per-layer collective.  This manual
    shard_map keeps fp32 *local* accumulation but psums on a bf16 wire, and
    passes w at its true (model, FSDP) storage sharding so weight gathers
    stay explicit and grad sync reduce-scatters.

    x: (B, S, K) with K sharded on "model"; w: (K, D).  Falls back to a
    plain matmul when no suitable mesh is active.
    """
    from repro.launch.sharding import get_mesh, in_manual_region

    mesh = get_mesh()
    k_dim, d_out = w.shape
    if (
        mesh is None
        or "model" not in mesh.axis_names
        or mesh.shape["model"] <= 1
        or k_dim % mesh.shape["model"] != 0
        or in_manual_region()  # nested manual shard_maps are rejected
    ):
        return x.astype(compute_dtype) @ w.astype(compute_dtype)

    from jax.sharding import PartitionSpec as P

    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]
    w_spec = P("model", dp) if d_out % n_dp == 0 else P("model", None)

    def body(x_loc, w_loc):
        if dp and w_spec[1] is not None:
            w_loc = jax.lax.all_gather(w_loc, dp, axis=1, tiled=True)
        y = jnp.einsum(
            "bsk,kd->bsd", x_loc.astype(compute_dtype),
            w_loc.astype(compute_dtype),
            preferred_element_type=jnp.float32,
        ).astype(compute_dtype)
        return jax.lax.psum(y, "model")

    b = x.shape[0]
    if not dp or b % n_dp != 0:
        # batch can't be dp-sharded (e.g. the batch=1 long-context decode
        # cells): the manual psum buys little there - use the plain path.
        return x.astype(compute_dtype) @ w.astype(compute_dtype)
    batch_spec = dp
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(batch_spec, None, "model"), w_spec),
        out_specs=P(batch_spec, None, None),
        axis_names=frozenset({"model"} | set(dp)),
        check_vma=True,  # vma tracking: transpose knows the psum output is
                         # replicated, avoiding a spurious backward psum
    )
    return fn(x, w)


def dense_init(key, d_in: int, d_out: int, dtype, n_stack: Optional[int] = None):
    shape = (d_in, d_out) if n_stack is None else (n_stack, d_in, d_out)
    return _init(key, shape, 1.0 / np.sqrt(d_in), dtype)


def rms_norm(x: jnp.ndarray, w: jnp.ndarray, eps: float) -> jnp.ndarray:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(dt) * w.astype(dt)


def rope_tables(seq_len: int, head_dim: int, theta: float, offset=0):
    """Rotary position tables; ``offset`` may be a traced scalar (decode)."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    pos = jnp.arange(seq_len, dtype=jnp.float32) + offset
    ang = pos[:, None] * freqs[None, :]
    return jnp.cos(ang), jnp.sin(ang)  # (S, half)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """x: (..., S, H, hd); cos/sin: (S, hd/2) or broadcastable (..., S, hd/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if cos.ndim == 2:  # (S, half) -> broadcast over batch and heads
        cos = cos[..., :, None, :]
        sin = sin[..., :, None, :]
    dt = x.dtype
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [x1f * cos - x2f * sin, x2f * cos + x1f * sin], axis=-1
    ).astype(dt)


# ----------------------------------------------------------------------------
# SwiGLU MLP (Megatron TP: w1/w3 column-parallel, w2 row-parallel)
# ----------------------------------------------------------------------------

def init_mlp(key, d_model: int, d_ff: int, dtype, n_stack=None):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w1": dense_init(k1, d_model, d_ff, dtype, n_stack),
        "w3": dense_init(k2, d_model, d_ff, dtype, n_stack),
        "w2": dense_init(k3, d_ff, d_model, dtype, n_stack),
    }


def mlp(x: jnp.ndarray, p, compute_dtype) -> jnp.ndarray:
    x = x.astype(compute_dtype)
    h = jax.nn.silu(x @ p["w1"].astype(compute_dtype))
    h = h * (x @ p["w3"].astype(compute_dtype))
    h = shard(h, dp_axes(), None, "model")
    return row_parallel_matmul(h, p["w2"], compute_dtype)


# ----------------------------------------------------------------------------
# Embedding + chunked vocab-parallel cross-entropy
# ----------------------------------------------------------------------------

def lm_logits(h: jnp.ndarray, w_out: jnp.ndarray) -> jnp.ndarray:
    """(B, D) hidden states @ (D, V) head -> f32 logits.

    The matmul runs at the head's own dtype with f32 accumulation, so a
    bf16 head is never copied to f32 (at vocab 151936 that copy is 4 bytes
    per parameter, 1.56 GB at d_model 2560).  Products of bf16 values are
    exact in f32, so this equals the f32 matmul of the widened operands up
    to the order of accumulation; an f32 head takes the f32 matmul."""
    return jnp.matmul(
        h.astype(w_out.dtype), w_out, preferred_element_type=jnp.float32
    )


def init_embed(key, vocab: int, d_model: int, dtype):
    return _init(key, (vocab, d_model), 1.0, dtype)


def embed(tokens: jnp.ndarray, table: jnp.ndarray, compute_dtype) -> jnp.ndarray:
    out = jnp.take(table, tokens, axis=0).astype(compute_dtype)
    return shard(out, dp_axes(), None, None)


def lm_loss_chunked(
    h: jnp.ndarray,            # (B, S, D) final hidden states
    w_out: jnp.ndarray,        # (D, V) lm head (vocab sharded on "model")
    labels: jnp.ndarray,       # (B, S) int32, -1 = ignore
    chunk: int = 1024,
) -> jnp.ndarray:
    """Mean next-token CE without ever materializing (B, S, V) logits.

    Scans over sequence chunks; each chunk's (B, c, V) logits are sharded
    vocab-wise on "model" so the live buffer per device is (B*c*V/16) fp32.
    """
    b, s, d = h.shape
    v = w_out.shape[-1]
    chunk = min(chunk, s)
    n_chunks = s // chunk
    rem = s - n_chunks * chunk

    def chunk_loss(hc, yc):
        logits = hc.astype(jnp.float32) @ w_out.astype(jnp.float32)
        logits = shard(logits, dp_axes(), None, "model")
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, yc[..., None].astype(jnp.int32).clip(0), axis=-1
        )[..., 0]
        valid = (yc >= 0).astype(jnp.float32)
        return jnp.sum((logz - gold) * valid), jnp.sum(valid)

    hs = h[:, : n_chunks * chunk].reshape(b, n_chunks, chunk, d)
    ys = labels[:, : n_chunks * chunk].reshape(b, n_chunks, chunk)

    def body(carry, xs):
        hc, yc = xs
        l, n = chunk_loss(hc, yc)
        return (carry[0] + l, carry[1] + n), None

    (tot, cnt), _ = jax.lax.scan(
        body, (jnp.zeros(()), jnp.zeros(())),
        (jnp.moveaxis(hs, 1, 0), jnp.moveaxis(ys, 1, 0)),
    )
    if rem:
        l, n = chunk_loss(h[:, n_chunks * chunk :], labels[:, n_chunks * chunk :])
        tot, cnt = tot + l, cnt + n
    return tot / jnp.maximum(cnt, 1.0)
