"""Plain float32 forward of a dense decoder, as the Qwen2 and Qwen3 model
cards describe it: token embedding; per layer RMSNorm, q/k/v projections
(with bias where ``qkv_bias``), per-head RMSNorm of q and k (where
``qk_norm``), rotary embedding (half-split rotation, base ``rope_theta``),
causal grouped-query softmax attention scaled by 1/sqrt(head_dim), output
projection, residual, RMSNorm, SwiGLU MLP, residual; final RMSNorm; LM head.

It imports nothing of the program.  It reads the benchmark's weight tree
(``bench/weights.py``) by leaf name and widens each layer's weights to f32
inside a scan over layers, so one layer at a time is held in f32.  Every
matmul runs at ``Precision.HIGHEST``.  Attention runs over blocks of
queries, so a long sequence never holds a (heads, T, T) score matrix.

``fp8=True`` is the control: every matmul operand is rounded to float8
e4m3 (absmax-scaled along its contraction axis) before an f32 product,
the precision step below the bf16 the configuration serves in.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 256
FP8_MAX = 448.0


def _q8(a, axis):
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, fp8):
    if fp8:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v, fp8):
    """q (T, H, hd), k/v (T, KVH, hd) -> (T, H*hd), causal."""
    t, h, hd = q.shape
    kvh = k.shape[1]
    g = h // kvh
    nb = t // Q_BLOCK
    qb = q.reshape(nb, Q_BLOCK, kvh, g, hd)
    kt = jnp.moveaxis(k, 1, 0)                      # (KVH, T, hd)
    vt = jnp.moveaxis(v, 1, 0)
    cols = jnp.arange(t, dtype=jnp.int32)
    if fp8:
        kt, vt = _q8(kt, -1), _q8(vt, 1)

    def block(args):
        qi, i = args                                # (B, KVH, G, hd)
        if fp8:
            qi = _q8(qi, -1)
        s = jnp.einsum("bkgd,ktd->kgbt", qi, kt, precision=HI)
        s = s / jnp.sqrt(jnp.float32(hd))
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK, dtype=jnp.int32)
        s = jnp.where(cols[None, None, None, :] <= rows[None, None, :, None],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if fp8:
            p = _q8(p, -1)
        o = jnp.einsum("kgbt,ktd->bkgd", p, vt, precision=HI)
        return o.reshape(Q_BLOCK, h * hd)

    out = jax.lax.map(block, (qb, jnp.arange(nb, dtype=jnp.int32)))
    return out.reshape(t, h * hd)


def _layer(conf, fp8, pos):
    h, kvh = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = conf.get("head_dim") or conf["hidden_size"] // h
    eps, theta = float(conf["rms_norm_eps"]), float(conf["rope_theta"])

    def body(x, p):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        a = p["attn"]
        t = x.shape[0]
        y = _rms(x, p["ln1"], eps)
        q, k, v = _mm(y, a["wq"], fp8), _mm(y, a["wk"], fp8), _mm(y, a["wv"], fp8)
        if conf["qkv_bias"]:
            q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
        q, k, v = q.reshape(t, h, hd), k.reshape(t, kvh, hd), v.reshape(t, kvh, hd)
        if conf["qk_norm"]:
            q, k = _rms(q, a["q_norm"], eps), _rms(k, a["k_norm"], eps)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        x = x + _mm(_attention(q, k, v, fp8), a["wo"], fp8)
        m = p["mlp"]

        def mlp(xb):
            y = _rms(xb, p["ln2"], eps)
            hb = jax.nn.silu(_mm(y, m["w1"], fp8)) * _mm(y, m["w3"], fp8)
            return xb + _mm(hb, m["w2"], fp8)

        # token blocks bound the (T, d_ff) intermediates of a long sequence
        nt = _blocks(t, 1536, Q_BLOCK)
        x = jax.lax.map(mlp, x.reshape(nt, t // nt, -1)).reshape(t, -1)
        return x, None

    return body


def _blocks(n: int, most: int, multiple: int = 1) -> int:
    """The fewest equal blocks of ``n`` with at most ``most`` each, each a
    multiple of ``multiple``."""
    for k in range(1, n + 1):
        if n % k == 0 and n // k <= most and (n // k) % multiple == 0:
            return k
    raise ValueError(f"no blocking of {n} by {most} in multiples of {multiple}")


def make_reader(conf: dict, t_pad: int, n_out: int, fp8: bool = False):
    """Jitted ``(params, tokens (t_pad,), lo, served (n_out,)) -> (gap,
    top)`` at positions ``lo .. lo + n_out - 1``: ``top`` the token the
    forward puts first, ``gap`` how far the logit of ``served`` lies below
    the best one.  ``t_pad`` is a multiple of 256; the LM head is applied
    in vocabulary blocks so no f32 copy of the whole head is held."""
    if t_pad % Q_BLOCK:
        raise ValueError(f"t_pad {t_pad} is not a multiple of {Q_BLOCK}")
    eps = float(conf["rms_norm_eps"])
    vocab = conf["vocab_size"]
    nv = _blocks(vocab, 16384)

    def fn(params, tokens, lo, served):
        pos = jnp.arange(t_pad, dtype=jnp.int32)
        x = params["embed"][tokens].astype(jnp.float32)
        x, _ = jax.lax.scan(_layer(conf, fp8, pos), x, params["blocks"])
        x = jax.lax.dynamic_slice_in_dim(x, lo, n_out, axis=0)
        x = _rms(x, params["final_norm"].astype(jnp.float32), eps)
        vb = vocab // nv

        def head_block(j):
            w = jax.lax.dynamic_slice_in_dim(params["lm_head"], j * vb, vb, axis=1)
            return _mm(x, w.astype(jnp.float32), fp8)

        logits = jax.lax.map(head_block, jnp.arange(nv, dtype=jnp.int32))
        logits = jnp.moveaxis(logits, 0, 1).reshape(n_out, vocab)
        best = jnp.max(logits, axis=-1)
        mine = jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]
        top = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return best - mine, top

    return jax.jit(fn)
