"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this driver builds the production mesh (16x16 single-pod or
2x16x16 multi-pod) from 512 placeholder host devices, constructs abstract
(ShapeDtypeStruct) model/optimizer/batch/cache stand-ins, jits the train or
serve step with the full sharding rules, and must ``.lower().compile()``
successfully.  It prints ``compiled.memory_analysis()`` (proves fit) and
``compiled.cost_analysis()`` (FLOPs/bytes for the roofline), parses
collective bytes from the post-SPMD HLO, and appends one JSON record per
cell under --out.

Usage:
  python -m repro.launch.dryrun --arch qwen3-14b --shape train_4k
  python -m repro.launch.dryrun --all                    # every cell, 16x16
  python -m repro.launch.dryrun --all --multi-pod        # every cell, 2x16x16
"""

import argparse
import json
import os
import time
import traceback


def _cell_id(arch, shape, multi_pod):
    return f"{arch}__{shape}__{'pod2' if multi_pod else 'pod1'}"


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             force: bool = False, save_hlo: bool = False,
             microbatches: int = 1):
    import jax
    import jax.numpy as jnp

    from repro.configs import SHAPES, get_config, shape_supported
    from repro.launch import params as P
    from repro.launch import roofline as R
    from repro.launch.mesh import make_production_mesh
    from repro.launch.sharding import set_mesh
    from repro.launch.steps import TrainHyper, init_train_state, make_train_step
    from repro.models.model_zoo import build
    from jax.sharding import NamedSharding, PartitionSpec

    os.makedirs(out_dir, exist_ok=True)
    cid = _cell_id(arch, shape_name, multi_pod)
    out_path = os.path.join(out_dir, cid + ".json")
    if os.path.exists(out_path) and not force:
        print(f"[dryrun] {cid}: cached")
        return json.load(open(out_path))

    cfg = get_config(arch)
    ok, reason = shape_supported(cfg, shape_name)
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16", "id": cid,
    }
    if not ok:
        rec.update(status="skipped", reason=reason)
        json.dump(rec, open(out_path, "w"), indent=1)
        print(f"[dryrun] {cid}: SKIPPED ({reason})")
        return rec

    spec = SHAPES[shape_name]
    kind = spec["kind"]
    seq, batch = spec["seq_len"], spec["global_batch"]
    bundle = build(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    set_mesh(mesh)
    t0 = time.time()

    try:
        with mesh:
            abs_state = jax.eval_shape(
                lambda: init_train_state(bundle, jax.random.PRNGKey(0))
            )
            pshard = P.param_shardings(mesh, abs_state["params"])
            repl = NamedSharding(mesh, PartitionSpec())
            state_shard = {
                "params": pshard,
                "opt": jax.tree.map(
                    lambda *_: None, abs_state["opt"],
                ),
            }
            # moments share the params' layout; step is replicated
            from repro.optim.adamw import AdamWState
            state_shard["opt"] = AdamWState(step=repl, mu=pshard, nu=pshard)

            if kind == "train":
                batch_abs = bundle.train_inputs(batch, seq)
                bshard = P.batch_shardings(mesh, batch_abs)
                step = make_train_step(
                    bundle, TrainHyper(microbatches=microbatches)
                )
                jitted = jax.jit(
                    step,
                    in_shardings=(state_shard, bshard),
                    out_shardings=(state_shard, repl),
                    donate_argnums=(0,),
                )
                lowered = jitted.lower(abs_state, batch_abs)
                n_tokens = batch * seq

            elif kind == "prefill":
                batch_abs = bundle.train_inputs(batch, seq)
                bshard = P.batch_shardings(mesh, batch_abs)

                def prefill_step(params, b):
                    # representative inference-prefill: forward + last-token
                    # logits (cache-filling variants share the same compute).
                    loss = bundle.loss_fn(params, b)
                    return loss

                jitted = jax.jit(
                    prefill_step,
                    in_shardings=(pshard, bshard),
                    out_shardings=repl,
                )
                lowered = jitted.lower(abs_state["params"], batch_abs)
                n_tokens = batch * seq

            else:  # decode
                sv = bundle.serve_inputs(batch, seq)
                cshard = P.cache_shardings(mesh, sv["cache"])
                extra_names = [
                    k for k in sv if k not in ("token", "pos", "cache")
                ]
                dp_shard = P.batch_shardings(
                    mesh, {k: sv[k] for k in ["token", "pos"] + extra_names}
                )

                def serve_step(params, token, pos, cache, *extras):
                    kw = dict(zip(extra_names, extras))
                    logits, new_cache = bundle.serve_step(
                        params, token, pos, cache, **kw
                    )
                    return jnp.argmax(logits, -1).astype(jnp.int32), new_cache

                jitted = jax.jit(
                    serve_step,
                    in_shardings=(
                        pshard, dp_shard["token"], dp_shard["pos"], cshard,
                        *[dp_shard[k] for k in extra_names],
                    ),
                    out_shardings=(dp_shard["token"], cshard),
                    donate_argnums=(3,),
                )
                lowered = jitted.lower(
                    abs_state["params"], sv["token"], sv["pos"], sv["cache"],
                    *[sv[k] for k in extra_names],
                )
                n_tokens = batch  # one new token per sequence

            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        print(f"[dryrun] {cid}: memory_analysis: {mem}")

        ca = compiled.cost_analysis()
        raw_flops = float(ca.get("flops", 0.0))
        raw_bytes = float(ca.get("bytes accessed", 0.0))
        print(
            f"[dryrun] {cid}: cost_analysis(raw, while-bodies-once): "
            f"flops={raw_flops:.3e} bytes={raw_bytes:.3e}"
        )
        hlo = compiled.as_text()
        from repro.launch import hlo_analysis as H
        hres = H.analyze(hlo)   # trip-count-aware dot FLOPs + collectives
        if save_hlo:
            with open(os.path.join(out_dir, cid + ".hlo.txt"), "w") as f:
                f.write(hlo)

        pc = R.count_params(abs_state["params"])
        mf = R.model_flops(
            pc["total"], pc["expert"], cfg.moe.top_k, cfg.moe.n_experts,
            n_tokens, kind="train" if kind == "train" else "decode",
        )
        n_dev = mesh.devices.size
        model_par = mesh.shape["model"]
        membytes = R.analytic_memory_bytes(
            cfg, kind, batch, seq, n_dev, model_par
        )
        flops = hres["dot_flops"]
        terms = R.roofline_terms(
            flops, membytes["bytes"], hres["collective_bytes"]
        )

        rec.update(
            status="ok",
            kind=kind,
            seq=seq,
            global_batch=batch,
            n_devices=n_dev,
            lower_s=round(t_lower, 2),
            compile_s=round(t_compile, 2),
            flops_per_device=flops,
            raw_cost_analysis=dict(flops=raw_flops, bytes=raw_bytes),
            hbm_bytes_per_device=membytes,
            collectives=dict(
                total_bytes=hres["collective_bytes"],
                bytes=hres["collective_bytes_by_kind"],
                counts=hres["collective_counts"],
            ),
            memory=dict(
                argument_bytes=mem.argument_size_in_bytes,
                output_bytes=mem.output_size_in_bytes,
                temp_bytes=mem.temp_size_in_bytes,
                alias_bytes=mem.alias_size_in_bytes,
                code_bytes=mem.generated_code_size_in_bytes,
            ),
            params=pc,
            model_flops_global=mf,
            model_flops_per_device=mf / n_dev,
            useful_flops_ratio=(mf / n_dev) / flops if flops else 0.0,
            roofline=terms,
        )
        json.dump(rec, open(out_path, "w"), indent=1)
        print(
            f"[dryrun] {cid}: OK  compute={terms['compute_s']:.4f}s "
            f"memory={terms['memory_s']:.4f}s "
            f"collective={terms['collective_s']:.4f}s "
            f"dominant={terms['dominant']} "
            f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)"
        )
        return rec
    except Exception as e:
        rec.update(status="error", error=f"{type(e).__name__}: {e}")
        json.dump(rec, open(out_path, "w"), indent=1)
        print(f"[dryrun] {cid}: ERROR {type(e).__name__}: {e}")
        traceback.print_exc()
        return rec
    finally:
        from repro.launch.sharding import set_mesh as _sm
        _sm(None)


def main():
    # 512 placeholder host devices; must precede the first jax backend use
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    from repro.configs import ASSIGNED_ARCHS, SHAPES

    archs = ASSIGNED_ARCHS if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    results = []
    for mp in meshes:
        for arch in archs:
            for shape in shapes:
                results.append(
                    run_cell(arch, shape, mp, args.out, force=args.force,
                             save_hlo=args.save_hlo,
                             microbatches=args.microbatches)
                )
    n_ok = sum(r.get("status") == "ok" for r in results)
    n_skip = sum(r.get("status") == "skipped" for r in results)
    n_err = sum(r.get("status") == "error" for r in results)
    print(f"[dryrun] done: {n_ok} ok, {n_skip} skipped, {n_err} errors")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
