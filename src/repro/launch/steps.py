"""Train / serve step builders over a ModelBundle."""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.models.model_zoo import ModelBundle
from repro.optim import adamw_init, adamw_update, cosine_warmup


@dataclasses.dataclass(frozen=True)
class TrainHyper:
    peak_lr: float = 3.0e-4
    warmup_steps: int = 100
    total_steps: int = 10000
    weight_decay: float = 0.1
    max_grad_norm: float = 1.0
    b1: float = 0.9
    b2: float = 0.95
    # gradient accumulation: split the global batch into this many
    # sequentially-processed microbatches (scan) - divides live activation
    # memory by the same factor at ~zero FLOP cost (EXPERIMENTS.md Perf
    # "remaining headroom" item 4).
    microbatches: int = 1


def init_train_state(bundle: ModelBundle, key) -> Dict[str, Any]:
    params = bundle.init(key)
    opt = adamw_init(params, moment_dtype=jnp.dtype(bundle.cfg.optimizer_dtype))
    return {"params": params, "opt": opt}


def make_train_step(bundle: ModelBundle, hyper: TrainHyper) -> Callable:
    def grads_of(params, batch):
        if hyper.microbatches <= 1:
            return jax.value_and_grad(bundle.loss_fn)(params, batch)
        mb = hyper.microbatches

        def split(x):
            b = x.shape[0]
            if b % mb:
                raise ValueError(f"batch {b} % microbatches {mb} != 0")
            return x.reshape(mb, b // mb, *x.shape[1:])

        micro = jax.tree.map(split, batch)

        def body(carry, mbatch):
            loss_sum, gacc = carry
            loss, g = jax.value_and_grad(bundle.loss_fn)(params, mbatch)
            gacc = jax.tree.map(
                lambda a, b_: a + b_.astype(jnp.float32), gacc, g
            )
            return (loss_sum + loss, gacc), None

        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )
        (loss_sum, gsum), _ = jax.lax.scan(body, (jnp.zeros(()), zeros), micro)
        inv = 1.0 / mb
        return loss_sum * inv, jax.tree.map(lambda g: g * inv, gsum)

    def train_step(state, batch):
        params, opt = state["params"], state["opt"]
        loss, grads = grads_of(params, batch)
        lr = cosine_warmup(
            opt.step, peak_lr=hyper.peak_lr, warmup_steps=hyper.warmup_steps,
            total_steps=hyper.total_steps,
        )
        params, opt, m = adamw_update(
            params, grads, opt, lr=lr, b1=hyper.b1, b2=hyper.b2,
            weight_decay=hyper.weight_decay, max_grad_norm=hyper.max_grad_norm,
        )
        return {"params": params, "opt": opt}, {
            "loss": loss, "lr": lr, **m,
        }

    return train_step


def make_compressed_train_step(
    bundle: ModelBundle, hyper: TrainHyper, mesh
) -> Callable:
    """Train step with int8 error-feedback gradient sync across "pod".

    Topology: data-parallel across pods over the slow inter-pod links and
    across "data" within each pod.  The cross-pod gradient leg is the
    bandwidth bottleneck at multi-pod scale; this variant averages each
    pod's gradients over "data" at full precision, then across pods with
    :func:`repro.optim.compressed_psum` - int8 wire + error feedback, 2x
    bytes vs bf16 / 4x vs fp32 on the DCN.

    The shard_map is manual over every mesh axis: with only "pod" manual
    and the other axes left to GSPMD, XLA's SPMD partitioner aborts the
    process on the scan-under-grad body (``spmd_partitioner_util.cc``
    partition-group check, JAX 0.9.0).  So parameters and optimizer state
    are replicated on every device (no FSDP or tensor parallelism inside
    a pod), and devices along "model" run the same batch shard.

    State carries the per-pod error-feedback residual tree with a leading
    (n_pods,) dim sharded over "pod".

    Returns ``train_step(state, batch) -> (state, metrics)`` with
    ``state = {"params", "opt", "comp": residual-tree}``.
    """
    from jax.sharding import PartitionSpec as PS

    from repro.optim.compression import CompressionState, compressed_psum

    if "pod" not in mesh.axis_names:
        raise ValueError("compressed train step needs a 'pod' mesh axis")
    n_pod = mesh.shape["pod"]

    def init_comp(params):
        return jax.tree.map(
            lambda p: jnp.zeros((n_pod,) + p.shape, jnp.float32), params
        )

    batch_axes = ("pod", "data") if "data" in mesh.axis_names else "pod"

    def per_pod(params, batch, comp_res):
        loss, grads = jax.value_and_grad(bundle.loss_fn)(params, batch)
        if "data" in mesh.axis_names:
            loss, grads = jax.lax.pmean((loss, grads), "data")
        res = jax.tree.map(lambda r: r[0], comp_res)  # strip local pod dim
        grads, new_comp = compressed_psum(
            grads, CompressionState(residual=res), "pod"
        )
        loss = jax.lax.pmean(loss, "pod")
        new_res = jax.tree.map(lambda r: r[None], new_comp.residual)
        return loss, grads, new_res

    def train_step(state, batch):
        params, opt = state["params"], state["opt"]
        in_specs = (
            jax.tree.map(lambda _: PS(), params),
            jax.tree.map(lambda x: PS(batch_axes), batch),
            jax.tree.map(lambda _: PS("pod"), state["comp"]),
        )
        out_specs = (
            PS(),
            jax.tree.map(lambda _: PS(), params),
            jax.tree.map(lambda _: PS("pod"), state["comp"]),
        )
        loss, grads, new_comp = jax.shard_map(
            per_pod, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )(params, batch, state["comp"])
        lr = cosine_warmup(
            opt.step, peak_lr=hyper.peak_lr, warmup_steps=hyper.warmup_steps,
            total_steps=hyper.total_steps,
        )
        params, opt, m = adamw_update(
            params, grads, opt, lr=lr, b1=hyper.b1, b2=hyper.b2,
            weight_decay=hyper.weight_decay, max_grad_norm=hyper.max_grad_norm,
        )
        return {"params": params, "opt": opt, "comp": new_comp}, {
            "loss": loss, "lr": lr, **m,
        }

    train_step.init_comp = init_comp
    return train_step


def make_serve_step(bundle: ModelBundle) -> Callable:
    """(params, token, pos, cache, **extras) -> (next_token, logits, cache)."""

    def serve_step(params, token, pos, cache, **extras):
        logits, new_cache = bundle.serve_step(params, token, pos, cache, **extras)
        next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_token, logits, new_cache

    return serve_step
