#!/usr/bin/env python3
"""Smoke run of the served path on one TPU at qwen3-4b's published widths.

    python3 chip_smoke.py               # one chip
    python3 chip_smoke.py --four-chips  # the --mesh 1x4 sharded serve

One process, run from a checkout of the repository.  With one chip it
builds qwen3-4b (36 layers, d_model 2560, 32/8 heads, head_dim 128, d_ff
9728, vocab 151936) with seeded random bf16 weights and serves 4 requests
(512-token prompts, 32 generated tokens each) through the paged engine
exactly as ``python -m repro.launch.serve --paged`` builds it: page size =
block_kv = 128, chunked prefill, bf16 page pool.  Then it checks what came
out, by the repository's own means:

  * every generated token lies in [0, vocab) and every logit of a dense
    prefill of the same prompts is finite;
  * the engine's token-by-token mode equals ``dense_greedy_reference`` on
    one short request (the contract of runtime/engine.py);
  * PASA at the paper's all-fp16 policy, head_dim 128, on biased and
    resonant inputs (tests/adversarial_inputs.py) stays finite and within
    the relative-RMSE bounds of tests/test_pasa_core.py and
    tests/test_kv_quant.py against an f32 reference;
  * the paged Pallas kernels (decode, prefill; bf16 and int8 pools),
    compiled for the chip, agree with their XLA fallbacks within the
    tolerance of tests/test_paged.py.

``--four-chips`` serves the same requests with ``--mesh 1x4 --paged`` (the
pool split kv-head-wise over 4 devices) beside the 1x1 serve, in the same
process, and compares the token streams: the bit-identity contract that
tests/test_sharded_serving.py pins on the CPU.

Earlier lines report each phase; the last line of standard output is one
JSON object, ``{"ok": true, "device": {...}}``.  The script exits non-zero
and prints no result when JAX finds no TPU, when it is not run from a
checkout, or when a check fails.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

ARCH = "qwen3-4b"
BATCH, PROMPT_LEN, GEN = 4, 512, 32
SERVE_ARGV = [
    "--arch", ARCH, "--paged", "--batch", str(BATCH),
    "--prompt-len", str(PROMPT_LEN), "--gen", str(GEN), "--page-size", "128",
]
#: token-by-token contract request: prompt and generated lengths
TBT_PROMPT, TBT_GEN = 16, 8
#: tests/test_paged.py: paged kernel vs its XLA fallback
KERNEL_ATOL, KERNEL_RTOL = 3e-3, 3e-2
#: tests/test_pasa_core.py: PASA fp16 relative RMSE on large-mean inputs
PASA_RMSE = 0.05
#: tests/test_kv_quant.py _sweep_bound: resonant inputs saturate the
#: softmax, so their relative RMSE pins finiteness and magnitude only
RESONANT_RMSE = 1.0
#: XLA compile options that round every fp16 intermediate as IEEE fp16.
#: By default XLA on a TPU keeps fp16 values at f32 inside a fusion (an
#: fp16 store followed by a load is elided), which the CPU does not do.
IEEE_FP16 = {"xla_allow_excess_precision": False}


def jit_ieee(jax, fn, *args):
    """``fn`` compiled with :data:`IEEE_FP16` for ``args``."""
    return jax.jit(fn).lower(*args).compile(compiler_options=IEEE_FP16)


class CheckFailed(Exception):
    pass


def check(cond, what):
    if not cond:
        raise CheckFailed(what)


def report(phase, **fields):
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{phase}] {body}", flush=True)


def _serve(eng, prompts, gen):
    reqs = [eng.submit([int(t) for t in p], gen) for p in prompts]
    eng.run_to_completion()
    return [list(r.generated) for r in reqs]


def phase_serve(jax, serve, args, mesh=None, param_sharding=None):
    """Build and serve as the CLI does; returns (bundle, params, prompts,
    streams) and reports compile/serve seconds on the host clock."""
    from repro.models.model_zoo import build

    cfg = serve.serving_config(args)
    bundle = build(cfg)
    t0 = time.perf_counter()
    params = serve.init_params(bundle, args.seed, param_sharding)
    jax.block_until_ready(params)
    init_s = time.perf_counter() - t0
    prompts = serve.make_prompts(cfg, args)
    eng, placement, num_pages, page_size = serve.make_paged_engine(
        args, bundle, params, mesh
    )
    # warm-up: one request of the same shape compiles the prefill and the
    # decode programs (gen >= 2 reaches the decode step)
    t0 = time.perf_counter()
    _serve(eng, prompts[:1], 2)
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    streams = _serve(eng, prompts, args.gen)
    serve_s = time.perf_counter() - t0
    n_tok = sum(len(s) for s in streams)
    check(n_tok == args.batch * args.gen, f"generated {n_tok} tokens")
    check(
        all(0 <= t < cfg.vocab_size for s in streams for t in s),
        "generated token outside [0, vocab)",
    )
    report(
        "serve", arch=cfg.arch_id, layers=cfg.n_layers, d_model=cfg.d_model,
        param_dtype=cfg.param_dtype, placement=repr(placement),
        pages=num_pages, page_size=page_size, requests=args.batch,
        prompt_len=args.prompt_len, tokens_generated=n_tok,
        init_s=f"{init_s:.3f}", compile_s=f"{compile_s:.3f}",
        serve_s=f"{serve_s:.3f}", clock="host",
    )
    return eng, bundle, params, prompts, streams


def phase_logits(jax, jnp, bundle, params, prompts, streams):
    """Dense fused prefill of the served prompts: all logits finite."""
    cfg = bundle.cfg
    cache = bundle.init_cache(len(prompts), PROMPT_LEN + GEN)
    fn = jax.jit(lambda p, t, c: bundle.prefill(p, t, c)[0])
    logits = fn(params, jnp.asarray(prompts), cache)
    finite = bool(jnp.isfinite(logits).all())
    top1 = [int(t) for t in jnp.argmax(logits, axis=-1)]
    agree = sum(int(a == s[0]) for a, s in zip(top1, streams))
    check(logits.shape == (len(prompts), cfg.vocab_size), "logits shape")
    check(finite, "non-finite logits")
    report(
        "logits", shape=tuple(logits.shape), dtype=logits.dtype,
        all_finite=finite, first_token_agrees_with_engine=f"{agree}/"
        f"{len(prompts)}",
    )


def phase_token_by_token(bundle, params, prompts):
    """runtime/engine.py contract: token-by-token mode ==
    dense_greedy_reference, for one short request."""
    from repro.runtime import ServeEngine, dense_greedy_reference

    prompt = [int(t) for t in prompts[0][:TBT_PROMPT]]
    total = TBT_PROMPT + TBT_GEN
    page = bundle.cfg.attention.block_kv
    eng = ServeEngine(
        bundle, params, max_batch=1, num_pages=1 + math.ceil(total / page),
        page_size=page, max_seq_len=total, chunked_prefill=False,
    )
    got = _serve(eng, [prompt], TBT_GEN)[0]
    want = dense_greedy_reference(bundle, params, prompt, TBT_GEN)
    first_diff = next(
        (i for i, (a, b) in enumerate(zip(got, want)) if a != b), None
    )
    report(
        "token_by_token", holds=got == want, engine=got, dense=want,
        first_mismatch=first_diff,
    )
    check(got == want, "token-by-token != dense_greedy_reference")


def phase_pasa_fp16(jax, jnp):
    """PASA at the all-fp16 policy, head_dim 128, vs an f32 reference."""
    sys.path.insert(0, str(ROOT / "tests"))
    import adversarial_inputs as adv

    from repro.core import FP16, FP16_FP32, flash_attention, naive_attention
    from repro.core import pasa_attention
    from repro.core.beta import DEFAULT_BETA
    from repro.core.numerics import rmse

    key = jax.random.PRNGKey(0)
    shape = (1, 4, 1280, 128)          # tests/test_pasa_core.py TestOverflow
    ks = jax.random.split(key, 3)
    uniform = [
        jax.random.uniform(k, shape, jnp.float32, minval=29.5, maxval=30.5)
        for k in ks
    ]
    cases = {"large_mean": (uniform, PASA_RMSE)}
    for case, bound in (("seq_bias", PASA_RMSE),
                        ("resonance_0", RESONANT_RMSE),
                        ("resonance_180", RESONANT_RMSE)):
        cases[case] = (
            adv.make_adversarial(
                case, key, q_shape=(1, 4, 1024, 128),
                kv_shape=(1, 4, 1024, 128),
            ),
            bound,
        )
    pasa = jax.jit(lambda q, k, v: pasa_attention(
        q, k, v, beta=DEFAULT_BETA, policy=FP16, block_kv=128
    ))
    fa16 = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, policy=FP16_FP32, block_kv=128
    ))
    def nonfinite(x):
        return float(1.0 - jnp.isfinite(x.astype(jnp.float32)).mean())

    ok = True
    for case, ((q, k, v), bound) in cases.items():
        with jax.default_matmul_precision("highest"):
            gold = naive_attention(q, k, v, dtype=jnp.float32)
        out = pasa(q, k, v)
        finite = bool(jnp.isfinite(out.astype(jnp.float32)).all())
        err = rmse(out, gold) if finite else float("inf")
        # as served, and (reported only) with IEEE fp16 intermediates
        ieee = jit_ieee(jax, pasa, q, k, v)(q, k, v)
        ieee_err = rmse(ieee, gold) if nonfinite(ieee) == 0 else float("inf")
        report(
            "pasa_fp16", case=case, finite=finite, rel_rmse=f"{err:.5f}",
            bound=bound, ieee_rel_rmse=f"{ieee_err:.5f}",
            fa_fp16_nonfinite_share=f"{nonfinite(fa16(q, k, v)):.4f}",
            fa_fp16_ieee_nonfinite_share=(
                f"{nonfinite(jit_ieee(jax, fa16, q, k, v)(q, k, v)):.4f}"
            ),
        )
        ok &= finite and err < bound
    check(ok, "PASA fp16 outside its bounds")


def _kernel_inputs(jax, jnp, np, key, kv_dtype):
    """Published-width decode/prefill operands over a shuffled page pool."""
    from repro.runtime.paged_cache import NULL_PAGE, quantize_kv_page

    b, kvh, g, d, page = BATCH, 8, 4, 128, 128
    kv_lens = np.array([640, 512, 384, 300], np.int32)
    max_pages = int(math.ceil(kv_lens.max() / page))
    n_pages = 1 + int(sum(math.ceil(n / page) for n in kv_lens)) + 2
    ks = jax.random.split(key, 4)
    raw_k = jax.random.normal(ks[0], (n_pages, page, kvh, d), jnp.float32)
    raw_k = raw_k + 2.0
    raw_v = jax.random.normal(ks[1], (n_pages, page, kvh, d), jnp.float32)
    order = np.random.default_rng(0).permutation(np.arange(1, n_pages))
    table = np.full((b, max_pages), NULL_PAGE, np.int32)
    nxt = 0
    for i, n in enumerate(kv_lens):
        for j in range(math.ceil(n / page)):
            table[i, j] = order[nxt]
            nxt += 1
    quant = {}
    if kv_dtype == "int8":
        valid = jnp.ones((n_pages, page), bool)
        kc, ksc, ksh = quantize_kv_page(raw_k, valid, jnp.int8)
        vc, vsc, vsh = quantize_kv_page(raw_v, valid, jnp.int8)
        pages = (kc, vc)
        quant = dict(k_scale=ksc, k_shift=ksh, v_scale=vsc, v_shift=vsh)
    else:
        pages = (raw_k.astype(jnp.bfloat16), raw_v.astype(jnp.bfloat16))
    q_dec = jax.random.normal(ks[2], (b, kvh, g, d), jnp.float32) + 1.0
    cs = 256
    q_pre = jax.random.normal(ks[3], (b, kvh * g, cs, d), jnp.float32)
    return dict(
        pages=pages, quant=quant, table=jnp.asarray(table),
        kv_len=jnp.asarray(kv_lens), start=jnp.asarray(kv_lens - cs),
        q_dec=q_dec.astype(jnp.bfloat16), q_pre=q_pre.astype(jnp.bfloat16),
    )


def phase_kernels(jax, jnp, np):
    """Each paged Pallas kernel compiled for the chip vs its XLA fallback."""
    import repro.kernels as K

    ok = True
    for kv_dtype in ("bf16", "int8"):
        x = _kernel_inputs(jax, jnp, np, jax.random.PRNGKey(7), kv_dtype)
        names = tuple(x["quant"])

        def dec(q, kp, vp, table, kv_len, *quant, use_kernel):
            return K.pasa_paged_decode(
                q, kp, vp, table, kv_len, use_kernel=use_kernel,
                **dict(zip(names, quant)),
            )

        def pre(q, kp, vp, table, start, kv_len, *quant, use_kernel):
            return K.pasa_paged_prefill(
                q, kp, vp, table, start, kv_len, use_kernel=use_kernel,
                **dict(zip(names, quant)),
            )

        calls = {
            "pasa_paged_decode": (dec, (
                x["q_dec"], *x["pages"], x["table"], x["kv_len"],
                *x["quant"].values(),
            )),
            "pasa_paged_prefill": (pre, (
                x["q_pre"], *x["pages"], x["table"], x["start"],
                x["kv_len"], *x["quant"].values(),
            )),
        }
        for name, (fn, args) in calls.items():
            kernel = jax.jit(
                lambda *a, fn=fn: fn(*a, use_kernel=True)
            ).lower(*args).compile()
            compiled = "tpu_custom_call" in kernel.as_text()
            got = np.asarray(kernel(*args), np.float32)
            # the kernel rounds like IEEE fp16 (kernels/fp16.py), as both
            # sides of tests/test_paged.py do on the CPU: so must the
            # fallback it is compared with
            want = np.asarray(jit_ieee(
                jax, lambda *a, fn=fn: fn(*a, use_kernel=False), *args
            )(*args), np.float32)
            finite = bool(np.isfinite(got).all())
            excess = np.abs(got - want) - (
                KERNEL_ATOL + KERNEL_RTOL * np.abs(want)
            )
            within = finite and bool((excess <= 0).all())
            report(
                "kernel", name=name, pool=kv_dtype, shape=got.shape,
                compiled_mosaic=compiled, finite=finite,
                max_abs_diff=f"{float(np.abs(got - want).max()):.3e}",
                within_test_paged_tolerance=within,
            )
            ok &= compiled and within
    check(ok, "a paged kernel failed on the chip")


def one_chip(jax, jnp, np, serve):
    args = serve.build_parser().parse_args(SERVE_ARGV)
    eng, bundle, params, prompts, streams = phase_serve(jax, serve, args)
    del eng
    phase_logits(jax, jnp, bundle, params, prompts, streams)
    phase_token_by_token(bundle, params, prompts)
    del params
    phase_pasa_fp16(jax, jnp)
    phase_kernels(jax, jnp, np)


def four_chips(jax, jnp, np, serve):
    """--mesh 1x4 --paged beside the 1x1 serve of the same requests."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_mesh
    from repro.launch.sharding import set_mesh

    check(len(jax.devices()) == 4, f"{len(jax.devices())} devices, need 4")
    args = serve.build_parser().parse_args(SERVE_ARGV)
    eng, bundle, params, prompts, ref = phase_serve(jax, serve, args)
    del eng, params

    args = serve.build_parser().parse_args(SERVE_ARGV + ["--mesh", "1x4"])
    mesh = make_mesh((1, 4), ("data", "model"))
    set_mesh(mesh)
    with mesh:
        grp, _, _, _, got = phase_serve(
            jax, serve, args, mesh, NamedSharding(mesh, P())
        )
    set_mesh(None)
    pool = grp.engines[0].pool
    shard_devices = {
        name: sorted(s.device.id for s in leaf.addressable_shards)
        for name, leaf in pool.items()
    }
    shard_shapes = {
        name: tuple(leaf.addressable_shards[0].data.shape)
        for name, leaf in pool.items()
    }
    in_use = [
        (d.memory_stats() or {}).get("bytes_in_use") for d in jax.devices()
    ]
    report(
        "four_chips", streams_identical=got == ref,
        pool_shard_devices=shard_devices, pool_shard_shapes=shard_shapes,
        bytes_in_use_per_device=in_use,
    )
    check(got == ref, "1x4 streams differ from the 1x1 serve")
    check(
        all(ids == [0, 1, 2, 3] for ids in shard_devices.values()),
        "pool shards not on four devices",
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the --mesh 1x4 sharded serve against "
                         "the 1x1 serve (needs 4 chips)")
    opts = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke.py: src/repro not found beside this script; "
              "run it from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke.py: needs a TPU, JAX found platform "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 3

    import jax.numpy as jnp
    import numpy as np

    from repro.launch import serve
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = Path(enable_compile_cache())
    cached = len(list(cache_dir.iterdir())) if cache_dir.is_dir() else 0
    report("device", platform=dev.platform, kind=repr(dev.device_kind),
           count=len(jax.devices()), compile_cache=str(cache_dir),
           cache_entries_at_start=cached)
    t0 = time.perf_counter()
    try:
        (four_chips if opts.four_chips else one_chip)(jax, jnp, np, serve)
    except CheckFailed as e:
        print(f"chip_smoke.py: check failed: {e}", file=sys.stderr)
        return 1
    stats = dev.memory_stats() or {}
    peak, limit = stats.get("peak_bytes_in_use"), stats.get("bytes_limit")
    report("memory", peak_bytes_in_use=peak, bytes_limit=limit,
           total_s=f"{time.perf_counter() - t0:.1f}")
    if peak is not None and limit is not None and peak >= limit:
        print("chip_smoke.py: peak memory at the device limit",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
