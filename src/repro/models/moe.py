"""Token-choice top-k MoE with sort-based capacity dispatch (EP over "model").

Dispatch strategy (compile-friendly at 1M-token batches, DESIGN.md):
  1. router -> top-k experts per token, renormalized gates;
  2. (token, slot) pairs sorted by expert id; position-within-expert computed
     via searchsorted on the sorted ids (O(Tk log Tk), no (T, E) one-hots);
  3. tokens scattered into an (E, capacity, D) buffer (mode="drop" beyond
     capacity - capacity_factor bounds the drop rate);
  4. per-expert GEMMs on the expert-sharded buffer;
  5. weighted scatter-add back to token order.

The (E, C, D) buffers and (E, D, F) weights are sharded on the expert axis
("model"), so dispatch/return become all-to-all-style collectives under GSPMD.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.launch.sharding import dp_axes, shard
from repro.models.layers import dense_init


def init_moe(key, cfg: ModelConfig, dtype, n_stack=None):
    e, d, f = cfg.moe.n_experts, cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 4)
    stack = lambda s: s if n_stack is None else (n_stack,) + s
    scale_in = 1.0 / np.sqrt(d)
    scale_out = 1.0 / np.sqrt(f)
    return {
        "router": dense_init(ks[0], d, e, jnp.float32, n_stack),
        "w1": (jax.random.normal(ks[1], stack((e, d, f)), jnp.float32)
               * scale_in).astype(dtype),
        "w3": (jax.random.normal(ks[2], stack((e, d, f)), jnp.float32)
               * scale_in).astype(dtype),
        "w2": (jax.random.normal(ks[3], stack((e, f, d)), jnp.float32)
               * scale_out).astype(dtype),
    }


def moe_ffn(x: jnp.ndarray, p, cfg: ModelConfig) -> jnp.ndarray:
    """x: (B, S, D) -> (B, S, D).  Dispatch strategy per cfg.moe.dispatch."""
    from repro.launch.sharding import get_mesh, in_manual_region

    mesh = get_mesh()
    if (
        cfg.moe.dispatch == "a2a"
        and mesh is not None
        and "model" in mesh.axis_names
        and mesh.shape["model"] > 1
        and cfg.moe.n_experts % mesh.shape["model"] == 0
        and not in_manual_region()
    ):
        nm = mesh.shape["model"]
        dp_n = 1
        for a in ("pod", "data"):
            if a in mesh.axis_names:
                dp_n *= mesh.shape[a]
        b, s, d = x.shape
        if (
            b % dp_n == 0
            and (b // dp_n) * s % nm == 0
            and d % dp_n == 0  # FSDP pass-through specs need divisibility
        ):
            return moe_ffn_a2a(x, p, cfg, mesh)
    return moe_ffn_gspmd(x, p, cfg)


def moe_ffn_gspmd(x: jnp.ndarray, p, cfg: ModelConfig) -> jnp.ndarray:
    """Baseline sharding-constraint dispatch (and the single-device path)."""
    cd = cfg.jnp_compute_dtype()
    b, s, d = x.shape
    e, k = cfg.moe.n_experts, cfg.moe.top_k
    t = b * s
    xf = x.reshape(t, d).astype(cd)

    # --- routing -----------------------------------------------------------
    logits = xf.astype(jnp.float32) @ p["router"].astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, top_e = jax.lax.top_k(probs, k)                    # (T, k)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)      # renormalize

    # --- sort-based dispatch -------------------------------------------------
    flat_e = top_e.reshape(-1)                               # (T*k,)
    flat_t = jnp.arange(t * k, dtype=jnp.int32) // k         # owning token
    flat_g = gate.reshape(-1)

    order = jnp.argsort(flat_e)                              # stable
    se = flat_e[order]
    st_tok = flat_t[order]
    sg = flat_g[order]
    pos_in_e = jnp.arange(t * k, dtype=jnp.int32) - jnp.searchsorted(
        se, se, side="left"
    ).astype(jnp.int32)

    cap = int(np.ceil(t * k / e * cfg.moe.capacity_factor))
    cap = max(cap, 1)
    keep = pos_in_e < cap
    # Out-of-capacity slots are routed to row index e (out of range) and
    # dropped by the scatter.
    se_safe = jnp.where(keep, se, e)

    buf = jnp.zeros((e, cap, d), cd)
    buf = buf.at[se_safe, pos_in_e].set(xf[st_tok], mode="drop")
    buf = shard(buf, "model", None, None)

    # --- expert GEMMs (E sharded on "model") ---------------------------------
    w1 = p["w1"].astype(cd)
    w3 = p["w3"].astype(cd)
    w2 = p["w2"].astype(cd)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w1))
    h = h * jnp.einsum("ecd,edf->ecf", buf, w3)
    h = shard(h, "model", None, None)
    y = jnp.einsum("ecf,efd->ecd", h, w2)                    # (E, C, D)

    # --- weighted return scatter ---------------------------------------------
    contrib = y[se_safe.clip(0, e - 1), pos_in_e.clip(0, cap - 1)]
    contrib = contrib * (sg * keep.astype(jnp.float32)).astype(cd)[:, None]
    out = jnp.zeros((t, d), cd).at[st_tok].add(contrib)
    out = shard(out.reshape(b, s, d), dp_axes(), None, None)
    return out


def _sorted_slots(sorted_keys: jnp.ndarray) -> jnp.ndarray:
    """Position of each element within its run of equal (sorted) keys."""
    n = sorted_keys.shape[0]
    return jnp.arange(n, dtype=jnp.int32) - jnp.searchsorted(
        sorted_keys, sorted_keys, side="left"
    ).astype(jnp.int32)


def moe_ffn_a2a(x: jnp.ndarray, p, cfg: ModelConfig, mesh) -> jnp.ndarray:
    """Explicit expert parallelism: all_to_all token routing over "model".

    Two-level dispatch (the production EP schedule):
      level 1 - tokens sorted by destination shard, packed into a fixed
        (n_shards, cap, D) buffer, exchanged with one all_to_all;
      level 2 - received tokens sorted by local expert, packed into the
        (E_local, cap2, D) GEMM buffer; everything here is shard-local.
    The return path reverses both levels (one more all_to_all).

    vs the GSPMD dispatch this replaces an (E, C, D)-replicating all-reduce
    per layer with two all_to_alls of the tokens actually routed - the
    measured win on kimi-k2 train_4k is ~40x collective bytes
    (EXPERIMENTS.md section Perf, iteration 2).  Only the "model" axis is
    manual; dp/FSDP sharding stays under GSPMD (partial-auto shard_map).
    """
    import jax as _jax

    cd = cfg.jnp_compute_dtype()
    b, s, d = x.shape
    e, k = cfg.moe.n_experts, cfg.moe.top_k
    nm = mesh.shape["model"]
    e_loc = e // nm
    from jax.sharding import PartitionSpec as P

    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_dp = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    b_loc = max(b // n_dp, 1)

    def body(xb, router_w, w1, w3, w2):
        # fully-manual: xb is the device-local (B_loc, S, D) token block,
        # REPLICATED across the model axis (the residual stream is model-
        # replicated).  Each model shard therefore owns the t/nm slice of
        # tokens at its axis index - without this split every shard routes
        # every token and the whole MoE is nm-x redundant (measured: the
        # first a2a version cost 3x baseline compute; EXPERIMENTS.md Perf
        # iteration 2).  Outputs are re-assembled with one bf16 all_gather.
        t_all = b_loc * s
        t = t_all // nm
        mi = jax.lax.axis_index("model")
        xf = jax.lax.dynamic_slice_in_dim(
            xb.reshape(t_all, d), mi * t, t, axis=0
        ).astype(cd)

        # Expert weights arrive at their true FSDP sharding and are gathered
        # here; the transpose of all_gather is psum_scatter, so the backward
        # pass reduce-SCATTERS expert grads into their FSDP shards instead of
        # all-reducing full per-device copies (ZeRO grad flow; EXPERIMENTS.md
        # Perf iteration 3).
        if dp:
            w1 = jax.lax.all_gather(w1, dp, axis=1, tiled=True)
            w3 = jax.lax.all_gather(w3, dp, axis=1, tiled=True)
            w2 = jax.lax.all_gather(w2, dp, axis=2, tiled=True)
        logits = xf.astype(jnp.float32) @ router_w.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate, top_e = jax.lax.top_k(probs, k)                  # (T, k)
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)

        flat_e = top_e.reshape(-1).astype(jnp.int32)           # (T*k,)
        flat_t = jnp.arange(t * k, dtype=jnp.int32) // k
        flat_g = gate.reshape(-1)
        flat_tgt = flat_e // e_loc                             # dest shard

        order = jnp.argsort(flat_tgt)
        s_tgt = flat_tgt[order]
        s_tok = flat_t[order]
        s_e = flat_e[order]
        s_g = flat_g[order]
        slot = _sorted_slots(s_tgt)
        cap = max(int(np.ceil(t * k / nm * cfg.moe.capacity_factor)), 1)
        keep = slot < cap
        tgt_safe = jnp.where(keep, s_tgt, nm)                  # drop lane

        send_x = jnp.zeros((nm, cap, d), cd).at[tgt_safe, slot].set(
            xf[s_tok], mode="drop"
        )
        send_le = jnp.full((nm, cap), e_loc, jnp.int32).at[tgt_safe, slot].set(
            s_e % e_loc, mode="drop"
        )  # e_loc == invalid marker for unfilled slots

        recv_x = jax.lax.all_to_all(
            send_x, "model", split_axis=0, concat_axis=0, tiled=False
        )
        recv_le = jax.lax.all_to_all(
            send_le, "model", split_axis=0, concat_axis=0, tiled=False
        )

        # ---- level 2: local per-expert packing ---------------------------
        rx = recv_x.reshape(nm * cap, d)
        rle = recv_le.reshape(nm * cap)
        order2 = jnp.argsort(rle)                              # invalid last
        s2_le = rle[order2]
        slot2 = _sorted_slots(s2_le)
        cap2 = max(int(np.ceil(nm * cap / e_loc * cfg.moe.capacity_factor)), 1)
        keep2 = jnp.logical_and(slot2 < cap2, s2_le < e_loc)
        le_safe = jnp.where(keep2, s2_le, e_loc)
        buf = jnp.zeros((e_loc, cap2, d), cd).at[le_safe, slot2].set(
            rx[order2], mode="drop"
        )

        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w1.astype(cd)))
        h = h * jnp.einsum("ecd,edf->ecf", buf, w3.astype(cd))
        y = jnp.einsum("ecf,efd->ecd", h, w2.astype(cd))       # (e_loc, cap2, d)

        # unpack level 2 back to recv-slot order
        y_sorted = (
            y[le_safe.clip(0, e_loc - 1), slot2.clip(0, cap2 - 1)]
            * keep2.astype(cd)[:, None]
        )
        y_recv = jnp.zeros((nm * cap, d), cd).at[order2].set(y_sorted)

        # ---- return all_to_all + source-side weighted combine ------------
        y_send = jax.lax.all_to_all(
            y_recv.reshape(nm, cap, d), "model", split_axis=0, concat_axis=0,
            tiled=False,
        ).reshape(nm * cap, d)
        contrib = (
            y_send[(s_tgt.clip(0, nm - 1)) * cap + slot.clip(0, cap - 1)]
            * (s_g * keep.astype(jnp.float32)).astype(cd)[:, None]
        )
        out_mine = jnp.zeros((t, d), cd).at[s_tok].add(contrib)
        out = jax.lax.all_gather(out_mine, "model", axis=0, tiled=True)
        return out.reshape(b_loc, s, d)

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(dp, None, None),              # tokens: DP-local
            P(None, None),                  # router: replicated (small)
            # expert weights at their true EP x FSDP storage sharding
            P("model", dp, None),
            P("model", dp, None),
            P("model", None, dp),
        ),
        out_specs=P(dp, None, None),
        axis_names=frozenset(mesh.axis_names),
        check_vma=False,
    )
    out = fn(
        x, p["router"].astype(jnp.float32), p["w1"], p["w3"], p["w2"]
    )
    return shard(out, dp_axes(), None, None)


def aux_load_balance_loss(logits: jnp.ndarray, top_e: jnp.ndarray, e: int):
    """Switch-style load-balance auxiliary (exposed for training recipes)."""
    probs = jax.nn.softmax(logits, axis=-1)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        jax.nn.one_hot(top_e[:, 0], e, dtype=jnp.float32), axis=0
    )
    return e * jnp.sum(me * ce)
