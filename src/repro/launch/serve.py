"""Batched serving driver: prefill + greedy decode with continuous batching.

Two cache backends:

  * **dense** (default, all families): one ``(L, B, max_len, kv_dim)`` cache
    allocated per batch - simple, but HBM scales with ``B * max_len`` even
    when sequences are short.  Families exposing a fused prefill
    (``bundle.prefill``: dense/moe transformers) consume the whole prompt in
    ONE forward pass instead of token-by-token teacher forcing, so TTFT is
    one model call rather than ``prompt_len`` decode steps.
  * **paged** (``--paged``; transformer families): the
    :class:`repro.runtime.ServeEngine` - fixed-size KV pages + per-sequence
    page tables + free-list allocator, with continuous batching (requests
    admitted whenever a slot and pages free up).  Prompts are prefetched in
    ``--prefill-chunk``-token chunks through the chunk-exact paged prefill,
    BATCHED across up to ``--prefill-batch`` still-prefilling requests per
    device call; ``--scheduler {fcfs,sjf,mixed}`` picks the admission /
    chunk-allocation / preemption policy, ``--step-token-budget`` caps the
    per-step token work (Sarathi-style mixing with the batched decode
    step), and ``--preemption`` lets a page-starved arrival page a running
    straggler out through the prefix cache.  All of these are
    latency-only: per-request outputs are bit-identical under every
    combination (repro/runtime/scheduler.py).  Pass
    ``--no-chunked-prefill`` for the PR-1 token-by-token reference mode.
    ``--prefix-cache`` additionally shares identical prompt-prefix K/V
    pages across requests through the radix prefix cache -
    bit-identically, see repro/runtime/prefix_cache.py.  ssm/hybrid keep
    the dense path: their recurrent state is O(1) per sequence, there is
    nothing to page.

Multi-tenant fleet (PR 8): ``--scheduler tenant`` serves through
:class:`repro.runtime.TenantQuotaPolicy` - per-tenant page/token quotas
(``--tenant-quotas 'bulk=8:32,interactive=16'``) and latency/throughput
SLO classes - and ``--routing {affinity,least,rr}`` picks the replica
-group placement policy (prefix-affinity by default: route to the
replica whose radix trie holds the longest cached prefix, falling back
to least-loaded; see runtime/README.md "Multi-tenant fleet").  Both are
latency-only knobs - streams stay bit-identical (tests/test_fleet.py).

Speculative decoding (PR 9): ``--speculate K --draft ngram`` turns on
self-speculative decoding on the paged route - a host-side
prompt-lookup drafter proposes up to K tokens per decoding row and a
single widened device step verifies them all; the engine accepts the
longest draft prefix that matches greedy argmax and restores the
pre-verify bytes of every rejected page slot, so token streams AND page
pool bytes are bit-identical to ``--speculate 0`` while repetitive
workloads finish in fewer engine steps (runtime/README.md
"Speculative decoding").

Sampling: ``--temperature`` / ``--top-k`` select per-request PRNG-keyed
sampling on the paged route (temperature 0 = greedy argmax, the
bit-exact default); keys derive from (request id, token index), so
sampled streams are reproducible and scheduling-invariant too.

Async pipelining: ``--async`` serves with ``pipeline_depth=1`` - the
engine plans and dispatches step N+1 while step N's tokens are still on
device, hiding host scheduling behind device execution; ``--sync``
(default) is the fully synchronous reference.  The two modes emit
bit-identical streams (tests/test_async_engine.py), so ``--async`` is a
pure wall-clock knob.  ``--stream`` prints each token as it is
MATERIALIZED (the engine's ``on_token`` callback - in async mode this
lags dispatch by one step), and ``--disconnect-after N`` simulates a
streaming client hanging up after N tokens of request 0: the driver
calls ``engine.cancel()`` between steps, which drains the pipeline,
frees the request's private pages, and donates its full prompt pages to
the prefix cache.

Observability: ``--trace FILE`` records every engine step's
plan/dispatch/retire spans and per-request lifecycle events to a Chrome
``trace_event`` file (Perfetto-loadable; ``--trace-format jsonl`` for
JSON-lines), ``--metrics`` prints the serving metrics registry snapshot
(TTFT histograms, queue/pool gauges, lifecycle counters), and
``--numerics-probe N`` samples the paper's overflow/resonance monitors
on live K pages every N steps.  All three are BIT-NEUTRAL - the
instrumented serve's streams are identical to the bare serve
(runtime/README.md "Observability").

Sharded paged serving: ``--mesh DxM --paged`` actually USES the mesh -
the ``data`` axis runs D engine replicas round-robin from one queue and
the ``model`` axis shards every replica's page pool (and its two jitted
step calls) kv-head-split across M devices, per-device pool HBM ~= 1/M
(repro/runtime/engine.py ``mesh`` doc).  Like every scheduling knob this
is bit-preserving: the DxM token streams match the 1x1 serve exactly
(tests/test_sharded_serving.py pins tokens AND page bytes at bf16 and
int8).  When the model's kv heads don't divide M the pool falls back to
replication (runtime/README.md documents the ring-PASA fallback rule).

Example (CPU-friendly):
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --reduced \
      --batch 4 --prompt-len 16 --gen 16 --mesh 1x1
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --reduced \
      --batch 4 --prompt-len 64 --gen 16 --mesh 1x1 --paged \
      --num-pages 64 --prefix-cache
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b --reduced \
      --batch 8 --prompt-len 64 --gen 16 --mesh 2x4 --paged
"""

from __future__ import annotations

import argparse
import time


def parse_tenant_quotas(spec):
    """Parse a ``--tenant-quotas`` spec into ``{tenant: TenantQuota}``.

    Format: comma-separated ``tenant=max_pages[:max_step_tokens]`` entries;
    an empty field means "unlimited" for that resource, e.g.
    ``bulk=8:32,interactive=16,best-effort=:64``.
    """
    from repro.runtime import TenantQuota

    quotas = {}
    for entry in spec.split(","):
        entry = entry.strip()
        if not entry:
            continue
        name, sep, body = entry.partition("=")
        name = name.strip()
        if not sep or not name:
            raise ValueError(
                f"bad --tenant-quotas entry {entry!r}: expected "
                "tenant=max_pages[:max_step_tokens]"
            )
        pages_s, _, toks_s = body.partition(":")
        try:
            max_pages = int(pages_s) if pages_s.strip() else None
            max_toks = int(toks_s) if toks_s.strip() else None
        except ValueError:
            raise ValueError(
                f"bad --tenant-quotas entry {entry!r}: fields must be ints"
            ) from None
        quotas[name] = TenantQuota(
            max_pages=max_pages, max_step_tokens=max_toks
        )
    return quotas


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=None)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--mesh", default="1x1",
                    help="DxM device mesh; on the paged route the data "
                         "axis runs D engine replicas and the model axis "
                         "shards each pool kv-head-split over M devices "
                         "(bit-identical to 1x1; see runtime/README.md)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--paged", action="store_true",
                    help="serve through the paged-KV continuous-batching "
                         "engine (transformer families)")
    ap.add_argument("--page-size", type=int, default=None,
                    help="tokens per KV page (default: the model's PASA "
                         "block length)")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="physical pages in the pool (default: sized to fit "
                         "the requested batch exactly)")
    ap.add_argument("--chunked-prefill", dest="chunked_prefill",
                    action="store_true", default=True,
                    help="paged route: prefill prompts in chunks through "
                         "the paged prefill path (default)")
    ap.add_argument("--no-chunked-prefill", dest="chunked_prefill",
                    action="store_false",
                    help="paged route: token-by-token prompt consumption "
                         "(the PR-1 reference mode)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="per-row chunk width of the batched prefill call; "
                         "multiple of the page size (default: 8 pages)")
    ap.add_argument("--scheduler", default="fcfs",
                    choices=("fcfs", "sjf", "mixed", "tenant"),
                    help="paged route: scheduling policy - fcfs (arrival "
                         "order, head-of-line blocking; the bit-preserving "
                         "default), sjf (shortest-job-first prefill, no "
                         "HOL blocking, aging guard), mixed (Sarathi-style "
                         "fair-share token-budget mixing), tenant "
                         "(multi-tenant quotas + latency/throughput "
                         "priority classes; see --tenant-quotas).  Outputs "
                         "are bit-identical across policies")
    ap.add_argument("--tenant-quotas", default=None, metavar="SPEC",
                    help="per-tenant quota spec for --scheduler tenant: "
                         "comma-separated tenant=max_pages[:max_step_"
                         "tokens] entries, e.g. 'bulk=8:32,interactive=16'"
                         " (empty field = unlimited)")
    ap.add_argument("--routing", default="affinity",
                    choices=("affinity", "least", "rr"),
                    help="replica-group request routing (multi-replica "
                         "meshes): affinity (longest cached prompt prefix "
                         "wins, least-loaded fallback; default), least "
                         "(least-loaded, round-robin tiebreak), rr "
                         "(strict rotation).  Routing never changes "
                         "output bits")
    ap.add_argument("--prefill-batch", type=int, default=None,
                    help="paged route: still-prefilling requests batched "
                         "into one prefill device call (default: --batch; "
                         "1 = the sequential baseline)")
    ap.add_argument("--step-token-budget", type=int, default=None,
                    help="paged route: global per-step token budget split "
                         "between decode rows (1 each) and prefill chunk "
                         "tokens (default: unlimited)")
    ap.add_argument("--preemption", action="store_true",
                    help="paged route: allow page-starved admissions to "
                         "preempt a running request to the prefix cache "
                         "(resume is bit-identical to an uninterrupted "
                         "serve)")
    ap.add_argument("--preempt-patience", type=int, default=4,
                    help="consecutive page-starved steps before a "
                         "preemption may trigger")
    ap.add_argument("--speculate", type=int, default=0, metavar="K",
                    help="paged route: self-speculative decoding - propose "
                         "up to K draft tokens per decoding row from a "
                         "host-side prompt-lookup drafter and verify them "
                         "in ONE widened device step; greedy accept keeps "
                         "the longest prefix matching argmax, so streams "
                         "AND page bytes are bit-identical to K=0 "
                         "(runtime/README.md 'Speculative decoding'). "
                         "Requires chunked prefill (0 = off)")
    ap.add_argument("--draft", default="ngram", choices=("ngram",),
                    help="--speculate draft proposer: ngram = longest-"
                         "suffix prompt/output lookup (no second model)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="paged route: sampling temperature (0 = greedy "
                         "argmax, bit-exact default)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="paged route: top-k truncation for sampling "
                         "(0 = full distribution; needs --temperature > 0 "
                         "to matter)")
    ap.add_argument("--sample-seed", type=int, default=0,
                    help="base PRNG seed for per-request sampling keys")
    ap.add_argument("--kv-dtype", default="bf16",
                    choices=("bf16", "fp8_e4m3", "int8"),
                    help="paged route: KV page pool storage dtype; "
                         "fp8_e4m3/int8 store shift-centered quantized "
                         "pages with per-page scale/shift sidecars "
                         "(~2x less pool HBM, RMSE-bounded accuracy)")
    ap.add_argument("--kv-quant-scale", default="absmax",
                    choices=("absmax", "quantile"),
                    help="quantized pools: page scale statistic - absmax "
                         "(exact range; the default and the attention-"
                         "accuracy recommendation) or quantile (clipped-"
                         "absmax: ~5x finer bulk-signal resolution but "
                         "measured WORSE end-to-end attention on outlier-"
                         "heavy traffic - see runtime/README.md; prefer "
                         "--kv-dtype fp8_e4m3 there)")
    ap.add_argument("--async", dest="pipelined", action="store_true",
                    default=False,
                    help="paged route: async pipelined serving "
                         "(pipeline_depth=1) - overlap host scheduling "
                         "with device execution; streams stay "
                         "bit-identical to --sync")
    ap.add_argument("--sync", dest="pipelined", action="store_false",
                    help="paged route: fully synchronous stepping "
                         "(default; the bit-identity reference)")
    ap.add_argument("--stream", action="store_true",
                    help="paged route: print each token as it is "
                         "materialized (the per-token on_token callback)")
    ap.add_argument("--disconnect-after", type=int, default=0,
                    help="paged route: simulate request 0's streaming "
                         "client disconnecting after N tokens - the "
                         "driver cancels it mid-stream (pages freed, "
                         "prompt pages donated to the prefix cache)")
    ap.add_argument("--prefix-cache", dest="prefix_cache",
                    action="store_true", default=False,
                    help="share identical prompt-prefix KV pages across "
                         "requests (radix cache; requires chunked prefill, "
                         "so it cannot combine with --no-chunked-prefill)")
    ap.add_argument("--no-prefix-cache", dest="prefix_cache",
                    action="store_false",
                    help="disable prompt-prefix KV page sharing (default)")
    ap.add_argument("--trace", default=None, metavar="FILE",
                    help="paged route: write a structured step trace "
                         "(plan/dispatch/retire spans + request lifecycle "
                         "events) to FILE - Chrome trace_event JSON "
                         "loadable in Perfetto / chrome://tracing, or "
                         "JSON-lines with --trace-format jsonl.  "
                         "Bit-neutral: the traced serve's streams are "
                         "identical to the untraced serve")
    ap.add_argument("--trace-format", default="chrome",
                    choices=("chrome", "jsonl"),
                    help="--trace file format (default: chrome)")
    ap.add_argument("--metrics", action="store_true",
                    help="paged route: collect the serving metrics "
                         "registry (TTFT histograms, queue/pool gauges, "
                         "lifecycle counters) and print its JSON snapshot "
                         "after the serve")
    ap.add_argument("--numerics-probe", type=int, default=0, metavar="N",
                    help="paged route: sample the online numerics-health "
                         "probe every N engine steps (0 = off) - "
                         "score-amplitude vs the fp16 ceiling, per-page "
                         "PASA shift magnitude, and K resonance on live "
                         "pages, read only at retirement drain points")
    return ap


def serving_config(args):
    """The model config a serve runs: ``--arch`` (``--reduced``) with
    bf16 weights - at published widths f32 weights do not fit one chip
    (qwen3-4b: ~17.6 GB f32 against a 16 GB TPU v5e) - and the requested
    KV page scale statistic."""
    import dataclasses

    from repro.configs import get_config

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    cfg = dataclasses.replace(cfg, param_dtype="bfloat16")
    if args.kv_quant_scale != "absmax":
        cfg = dataclasses.replace(
            cfg,
            attention=dataclasses.replace(
                cfg.attention, kv_quant_scale=args.kv_quant_scale
            ),
        )
    return cfg


def init_params(bundle, seed: int, sharding=None):
    """Seeded random weights, placed at ``sharding`` (default: the default
    device).  One jitted program: each leaf's f32 draw fuses with its
    cast, so no f32 copy of a leaf is ever held beside the bf16 params."""
    import jax

    placed = {} if sharding is None else {"out_shardings": sharding}
    return jax.jit(bundle.init, **placed)(jax.random.PRNGKey(seed))


def make_prompts(cfg, args):
    import numpy as np

    rng = np.random.default_rng(args.seed)
    return rng.integers(
        0, cfg.vocab_size, (args.batch, args.prompt_len), dtype=np.int32
    )


def main(argv=None):
    args = build_parser().parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_mesh, make_production_mesh
    from repro.launch.sharding import set_mesh
    from repro.launch.steps import make_serve_step
    from repro.models.model_zoo import build

    enable_compile_cache()
    cfg = serving_config(args)
    if args.mesh == "prod":
        mesh = make_production_mesh()
    else:
        d, m = (int(x) for x in args.mesh.split("x"))
        mesh = make_mesh((d, m), ("data", "model"))
    set_mesh(mesh)
    max_len = args.max_len or (args.prompt_len + args.gen + 8)

    bundle = build(cfg)
    # a multi-device serve replicates the weights on every device: create
    # them there, not on device 0 and then copied
    replicated = NamedSharding(mesh, PartitionSpec()) if mesh.size > 1 else None
    with mesh:
        params = init_params(bundle, args.seed, replicated)
        prompts = make_prompts(cfg, args)

        if args.paged:
            return _serve_paged(args, bundle, params, prompts, mesh)

        cache = bundle.init_cache(args.batch, max_len)
        step = jax.jit(make_serve_step(bundle))

        extras = {}
        if cfg.family == "vlm":
            extras["vision_embeds"] = jnp.zeros(
                (args.batch, cfg.n_image_tokens, cfg.vision_dim), jnp.bfloat16
            )

        t0 = time.time()
        generated = []
        if bundle.prefill is not None and not extras:
            # Fused prefill: the whole prompt in one forward pass - the
            # dense route's replacement for token-by-token consumption.
            pf = jax.jit(lambda p, t, c: bundle.prefill(p, t, c))
            logits, cache = pf(params, jnp.asarray(prompts), cache)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            jax.block_until_ready(tok)
            t_first = time.time() - t0
            generated.append(np.asarray(tok))
            for i in range(args.prompt_len, args.prompt_len + args.gen - 1):
                pos = jnp.full((args.batch,), i, jnp.int32)
                tok, _, cache = step(params, tok, pos, cache)
                generated.append(np.asarray(tok))
            n_steps = 1 + args.gen - 1
        else:
            # family-generic token-by-token route (ssm/hybrid/vlm/audio)
            tok = jnp.asarray(prompts[:, 0])
            t_first = None
            for i in range(args.prompt_len + args.gen - 1):
                pos = jnp.full((args.batch,), i, jnp.int32)
                nxt, logits, cache = step(params, tok, pos, cache, **extras)
                if i + 1 < args.prompt_len:
                    tok = jnp.asarray(prompts[:, i + 1])
                else:
                    if t_first is None:
                        jax.block_until_ready(nxt)
                        t_first = time.time() - t0
                    tok = nxt
                    generated.append(np.asarray(nxt))
            n_steps = args.prompt_len + args.gen - 1
        dt = time.time() - t0
        gen = np.stack(generated, axis=1)
        print(f"generated {gen.shape} tokens in {dt:.2f}s "
              f"({1000*dt/max(n_steps,1):.1f} ms/step, "
              f"TTFT {1000*t_first:.1f} ms)")
        print("sample:", gen[0][:16])
        return gen


def make_paged_engine(args, bundle, params, mesh=None, **engine_kwargs):
    """Build the paged engine the CLI serves through.

    The mesh is USED here (not just activated): with ``--mesh DxM``,
    the ``data`` axis becomes D engine replicas fed round-robin from one
    queue (:class:`repro.runtime.EngineReplicaGroup`) and the ``model``
    axis shards each replica's page pool kv-head-split across its M
    devices (``ServeEngine(mesh=...)``) - both bit-preserving, so the
    DxM serve's streams match the 1x1 serve token for token.  Extra
    ``engine_kwargs`` (telemetry, on_token) pass through.

    Returns ``(engine, placement, num_pages, page_size)``."""
    import math

    from repro.runtime import EngineReplicaGroup, ServeEngine

    page_size = (
        args.page_size if args.page_size is not None
        else bundle.cfg.attention.block_kv
    )
    if page_size < 1:
        raise ValueError(f"--page-size must be >= 1, got {page_size}")
    total = args.prompt_len + args.gen
    chunk = args.prefill_chunk
    if chunk is not None and chunk % page_size:
        raise ValueError(
            f"--prefill-chunk {chunk} must be a multiple of the page size "
            f"{page_size}"
        )
    shape = dict(mesh.shape) if mesh is not None else {}
    n_data = int(shape.get("data", 1))
    n_model = int(shape.get("model", 1))
    batch_per = math.ceil(args.batch / n_data)
    need = math.ceil(total / page_size) * batch_per
    num_pages = args.num_pages or need + 1  # +1: reserved null page
    scheduler = args.scheduler
    if args.tenant_quotas is not None:
        if args.scheduler != "tenant":
            raise ValueError("--tenant-quotas requires --scheduler tenant")
        from repro.runtime import TenantQuotaPolicy

        scheduler = TenantQuotaPolicy(
            parse_tenant_quotas(args.tenant_quotas),
            patience=max(args.preempt_patience, 1),
        )
    engine_kwargs.update(
        max_batch=batch_per, num_pages=num_pages, page_size=page_size,
        max_seq_len=total,
        chunked_prefill=args.chunked_prefill,
        prefill_chunk=chunk,
        prefix_cache=args.prefix_cache,
        cache_dtype=args.kv_dtype,
        scheduler=scheduler,
        prefill_batch=args.prefill_batch,
        step_token_budget=args.step_token_budget,
        preemption=args.preemption,
        preempt_patience=args.preempt_patience,
        temperature=args.temperature,
        top_k=args.top_k,
        sample_seed=args.sample_seed,
        pipeline_depth=1 if args.pipelined else 0,
        speculate=args.speculate,
        draft=args.draft,
    )
    if mesh is not None and (n_data > 1 or n_model > 1):
        eng = EngineReplicaGroup(
            bundle, params, mesh, routing=args.routing, **engine_kwargs
        )
        placement = f"{n_data} replicas x model={n_model} pool shards"
    else:
        eng = ServeEngine(bundle, params, **engine_kwargs)
        placement = "1 device"
    return eng, placement, num_pages, page_size


def _serve_paged(args, bundle, params, prompts, mesh=None):
    """Serve the same workload through the paged-KV engine
    (:func:`make_paged_engine`)."""
    import numpy as np

    from repro.runtime import Telemetry

    engine_kwargs = {}

    # observability: one Telemetry per serve, layers switched by flags.
    # Bit-neutral - every hook reads host state only; the numerics probe
    # reads pages at retirement drain points (runtime/telemetry.py).
    telemetry = None
    if args.trace or args.metrics or args.numerics_probe:
        telemetry = Telemetry(
            tracing=args.trace is not None,
            metrics=args.metrics,
            numerics_every=args.numerics_probe,
        )
        engine_kwargs["telemetry"] = telemetry

    # streaming emission: tokens arrive through on_token as they are
    # MATERIALIZED (at retirement - one step behind dispatch in --async).
    # --disconnect-after simulates request 0's client hanging up: the
    # callback only FLAGS the disconnect; the driver calls cancel()
    # between steps (never from inside a retirement).
    hangup: list = []
    if args.stream or args.disconnect_after:
        def on_token(r, idx, tok):
            if args.stream:
                print(f"[stream] req {r.req_id} #{idx}: {tok}")
            if (args.disconnect_after and r.req_id == 0
                    and idx + 1 >= args.disconnect_after
                    and 0 not in hangup):
                hangup.append(0)
        engine_kwargs["on_token"] = on_token

    eng, placement, num_pages, page_size = make_paged_engine(
        args, bundle, params, mesh, **engine_kwargs
    )
    reqs = [eng.submit(list(p), args.gen) for p in prompts]
    t0 = time.time()
    if args.stream or args.disconnect_after:
        cancelled = set()
        while not eng.idle:
            eng.step()
            while hangup:
                rid = hangup.pop()
                if rid not in cancelled and eng.cancel(rid):
                    cancelled.add(rid)
                    print(f"[stream] req {rid} client disconnected -> "
                          "cancelled (pages reclaimed)")
        eng.drain()       # stream boundary: flush trailing emissions
    else:
        eng.run_to_completion()
    dt = time.time() - t0
    # a cancelled request's stream is legitimately short: right-pad its
    # row with -1 so the report keeps one row per submitted request
    gen = np.stack([
        np.asarray(
            list(r.generated) + [-1] * (args.gen - len(r.generated)),
            np.int32,
        )
        for r in reqs
    ], axis=0)
    st = eng.stats()
    # measured from SUBMIT so queueing counts - and so the number stays
    # meaningful under --preemption (re-admission overwrites admit_step,
    # while first_token_step keeps the original emission)
    ttft_steps = [
        r.first_token_step - r.submit_step + 1 for r in reqs
        if r.first_token_step >= 0    # cancelled before its first token
    ]
    mode = ("chunked" if args.chunked_prefill else "token-by-token")
    mode += "/async" if args.pipelined else "/sync"
    # the versioned stats schema shares every key between ServeEngine and
    # EngineReplicaGroup (the group view is a true aggregation), so no
    # engine-vs-group branching is needed here
    n_tokens = int(sum(len(r.generated) for r in reqs))
    print(f"[paged/{mode}/{st['scheduler']}] generated {gen.shape} tokens "
          f"in {dt:.2f}s ({1000*dt/max(st['steps'],1):.1f} ms/step, "
          f"{n_tokens/max(dt, 1e-9):.1f} tok/s wall-clock), "
          f"pool={st['cache_bytes']/1e6:.2f} MB total {st['pool_dtype']} "
          f"({st['cache_bytes_per_device']/1e6:.2f} MB/device; {placement}; "
          f"{num_pages} pages x {page_size} tok per replica), "
          f"TTFT {np.mean(ttft_steps):.1f} engine steps, "
          f"{st['preemptions']} preemptions, "
          f"{st['cancellations']} cancellations")
    if args.speculate:
        sp = st["spec"]
        print(f"[speculate k={args.speculate}/{args.draft}] "
              f"{sp['proposed']} drafts proposed, {sp['accepted']} accepted "
              f"({sp['accepted']/max(sp['proposed'],1):.2f} accept rate), "
              f"{sp['verify_steps']} verify steps, "
              f"{sp['rollbacks']} rollbacks; "
              f"{st['steps']/max(n_tokens,1):.2f} engine steps/token")
    if args.prefix_cache and st["prefix_cache"] is not None:
        pc = st["prefix_cache"]
        print(f"[prefix-cache] {pc['cached_pages']} pages cached, "
              f"{pc['hits']} page hits / {pc['misses']} misses, "
              f"{pc['evictions']} evictions, {pc['donations']} donations")
    if telemetry is not None:
        _report_telemetry(args, telemetry)
    print("sample:", gen[0][:16])
    return gen


def _report_telemetry(args, telemetry):
    """Write the trace file and/or print the metrics snapshot."""
    import json

    if args.trace:
        if args.trace_format == "jsonl":
            n = telemetry.tracer.write_jsonl(args.trace)
        else:
            n = telemetry.tracer.write_chrome_trace(args.trace)
        dropped = telemetry.tracer.dropped
        print(f"[trace] {n} events -> {args.trace} "
              f"({args.trace_format}; {dropped} dropped by the ring)"
              + ("" if args.trace_format == "jsonl"
                 else "; open in https://ui.perfetto.dev"))
    if args.metrics:
        snap = telemetry.metrics_snapshot()
        print("[metrics]", json.dumps(snap, indent=2, sort_keys=True))
    if args.numerics_probe:
        probes = [telemetry.probe] + [
            c.probe for c in telemetry._children if c.probe is not None
        ]
        last = next(
            (p.last for p in probes if p is not None and p.last), None
        )
        if last is not None:
            print(f"[numerics] fp16_margin={last['fp16_margin']:.1f} "
                  f"score_amp_max={last['score_amp_max']:.1f} "
                  f"shift_mag_max={last['shift_mag_max']:.3f} "
                  f"resonance_max={last['resonance_max']:.3f} "
                  f"({last['pages_sampled']} pages sampled)")


if __name__ == "__main__":
    main()
