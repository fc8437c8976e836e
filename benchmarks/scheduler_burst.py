"""Bursty-arrival scheduling: TTFT under FCFS / SJF / mixed policies,
batched vs B=1 multi-request prefill, preempt-to-page-out, and the
model-axis-sharded multi-device serve.

A staggered burst (one request submitted per engine step, mixed prompt
lengths, more requests than batch slots) is served through the paged
engine under each scheduler configuration.  Reported per config:

  * mean / worst time-to-first-token in ENGINE STEPS measured from
    SUBMIT (so queueing + prefill serialization both count) - step
    counts are deterministic and hardware-independent, which is what
    makes the JSON trajectory (benchmarks/BENCH_serving.json) diffable
    across PRs;
  * steps to drain the burst and wall-clock tokens/s (CPU gather
    fallback - indicative only).

The headline comparison: with ``prefill_batch=1`` (the pre-refactor
schedule) prefill chunks of concurrent requests serialize - one request's
chunk per step - so TTFT grows linearly down the queue; batched
multi-request prefill advances every admitted prompt each step and
strictly reduces mean TTFT under the same arrivals (asserted in
tests/test_scheduler.py; this benchmark records the trajectory).
Outputs are bit-identical across every row - scheduling is latency-only.

Wall-clock rows (``scheduler_burst/wallclock_{sync,async}``): a
decode-heavy burst (short prompts, long generations - the regime where
per-step host work is largest relative to device work) timed for real,
sync (``pipeline_depth=0``) vs async (``pipeline_depth=1``), with warmed
jits and ``block_until_ready`` only at stream boundaries.  Reported:
median-of-reps wall-clock tokens/sec and p50/p99 TTFT in SECONDS
(measured submit -> token MATERIALIZED through the streaming callback,
so the async row pays its one-step emission lag honestly).  The streams
are asserted bit-identical across modes before any number is recorded -
the async speedup is pure overlap, not a schedule change.  These rows
complement (never replace) the deterministic step-count rows: steps are
the diffable cross-PR contract, wall-clock is the honest-throughput
claim ROADMAP flagged as missing.

The speculative-decode rows (PR 9): ``scheduler_burst/spec_decode_off``
vs ``spec_decode_on`` serve a repetitive burst (constant-token prompts,
near-cyclic greedy continuations) plainly and with K=6 n-gram
self-speculation; bit-identity of streams AND page bytes is asserted
in-run before anything is recorded, and the deterministic
steps-per-token of the on-row is the acceptance metric (<= 0.6,
enforced by benchmarks/run.py).  On the CPU gather fallback the widened
verify costs ~K+1 decode-steps of device work per engine step, so the
wall tokens/s sidecar penalizes speculation here - on real accelerators
the verify is one memory-bound pass and steps-per-token is the
latency proxy that matters.

The fleet rows (PR 8): ``scheduler_burst/tenant_isolation`` serves a
latency-class tenant into a long-prompt flood three ways - alone, under
tenant-blind FCFS, and under ``TenantQuotaPolicy`` with the flooder
quota'd - and asserts the quota'd victim p99 TTFT stays within 10% of
the isolated serve (streams bit-identical blind vs tenant: quotas are
latency-only).  ``scheduler_burst/prefix_affinity_2rep`` pushes a
shared-system-prompt burst through a 2-replica group (subprocess, 2
forced host devices) under prefix-affinity vs blind rotation, recording
cache hit rate and TTFT per mode with streams asserted identical across
routing.

The multi-device row (``scheduler_burst/multidev_2x4``) re-runs the same
staggered burst through :class:`repro.runtime.EngineReplicaGroup` on a
``2x4`` host-device mesh - 2 data-parallel engine replicas, each pool
kv-head-sharded over 4 model devices - in a SUBPROCESS (XLA pins the
host device count at backend init, so the 8-device run cannot share this
interpreter).  It records mean/worst TTFT, drain steps, and the
measured per-device pool HBM vs the replica's global pool (the
~1/model-axis-size acceptance metric), and asserts inside the subprocess
that the sharded streams are bit-identical to a 1-device serve of the
same burst.
"""

from __future__ import annotations

import gc
import json
import math
import os
import subprocess
import sys
import time
from collections import deque

import jax
import numpy as np

from repro.configs import get_config
from repro.models.model_zoo import build
from repro.runtime import (
    ServeEngine, TenantQuota, TenantQuotaPolicy,
)

PROMPTS = (96, 32, 96, 64, 32, 64)   # staggered burst, mixed lengths
GEN = 4
PAGE = 8
CHUNK = 32
BATCH = 4
ARRIVAL_GAP = 1                      # engine steps between submits
BUDGET = CHUNK + BATCH               # mixed row: chunk tokens + decode rows


def _bundle():
    cfg = get_config("qwen3-4b").reduced()
    bundle = build(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    return cfg, bundle, params


def burst_metrics(bundle, params, prompts, **engine_kwargs):
    """Serve a staggered burst; returns deterministic step metrics + wall
    throughput.  ``engine_kwargs`` pass through to :class:`ServeEngine`."""
    total = max(len(p) for p in prompts) + GEN
    num_pages = 1 + sum(math.ceil((len(p) + GEN) / PAGE) for p in prompts)
    eng = ServeEngine(
        bundle, params, max_batch=BATCH, num_pages=num_pages,
        page_size=PAGE, max_seq_len=total, prefill_chunk=CHUNK,
        **engine_kwargs,
    )
    # warm both jitted calls outside the timed region (gen=2 so the decode
    # step compiles too, not just the prefill call)
    eng.submit(list(prompts[0][:2]), 2)
    eng.run_to_completion()

    pending = deque(
        (eng.steps + i * ARRIVAL_GAP, p) for i, p in enumerate(prompts)
    )
    reqs = []
    s0 = eng.steps
    t0 = time.perf_counter()
    while pending or not eng.idle:
        while pending and pending[0][0] <= eng.steps:
            reqs.append(eng.submit(list(pending.popleft()[1]), GEN))
        eng.step()
    dt = time.perf_counter() - t0
    ttfts = [r.first_token_step - r.submit_step + 1 for r in reqs]
    toks = sum(len(r.prompt) + r.max_new_tokens - 1 for r in reqs)
    return {
        "mean_ttft_steps": float(np.mean(ttfts)),
        "max_ttft_steps": int(np.max(ttfts)),
        "drain_steps": eng.steps - s0,
        "preemptions": eng.preemptions,
        "tokens_per_s": toks / dt,
        "generated": [r.generated for r in reqs],
    }


CONFIGS = (
    ("fcfs_b1", dict(scheduler="fcfs", prefill_batch=1)),
    ("fcfs_batched", dict(scheduler="fcfs")),
    ("sjf_batched", dict(scheduler="sjf")),
    ("mixed_batched", dict(scheduler="mixed", step_token_budget=BUDGET)),
)

# ------------------------------------------------ wall-clock sync/async --

# Decode-heavy burst: short prompts, long generations - decode steps
# dominate, which is where the per-step host turnaround (plan + readback)
# is largest relative to device work and pipelining has something to hide.
# Large enough (16 requests x 32 tokens) that one rep is hundreds of
# steps - timing noise on a shared host must not drown the overlap.
WALL_PROMPTS = (24, 16, 32, 16, 24, 16, 32, 24) * 2
WALL_GEN = 32
WALL_REPS = 6          # even: the alternating pair order stays balanced


def wallclock_metrics(reps: int = WALL_REPS):
    """Real-time sync / async comparison on the decode-heavy burst.

    Method: per mode, warm BOTH jitted calls with a throwaway request,
    then serve the staggered burst ``reps`` times; the timed region syncs
    with the device only at the stream boundary (``drain()`` +
    ``block_until_ready`` on the pool).  Per-request TTFT-seconds are
    taken submit -> first token MATERIALIZED via the ``on_token``
    streaming callback - the latency a streaming client actually sees,
    including the async mode's one-step emission lag.  Streams are
    asserted bit-identical across modes (the overlap must not change the
    schedule's outputs, only its wall-clock)."""
    cfg, bundle, params = _bundle()
    rng = np.random.default_rng(1)
    prompts = [list(rng.integers(0, cfg.vocab_size, n)) for n in WALL_PROMPTS]
    total = max(len(p) for p in prompts) + WALL_GEN
    num_pages = 1 + sum(
        math.ceil((len(p) + WALL_GEN) / PAGE) for p in prompts
    )
    modes = (("sync", 0), ("async", 1))
    rates = {m: [] for m, _ in modes}
    ttfts = {m: [] for m, _ in modes}
    streams: dict = {}

    def run_once(mode, depth):
        gc.collect()          # level allocator/GC state across the pair
        clocks: dict = {}

        def on_token(r, idx, tok):
            if idx == 0 and r.req_id in clocks:   # warmup req has no clock
                ttfts[mode].append(time.perf_counter() - clocks[r.req_id])

        eng = ServeEngine(
            bundle, params, max_batch=BATCH, num_pages=num_pages,
            page_size=PAGE, max_seq_len=total, prefill_chunk=CHUNK,
            pipeline_depth=depth, on_token=on_token,
        )
        eng.submit(list(prompts[0][:2]), 2)
        eng.run_to_completion()                   # warm both jitted calls
        pending = deque(
            (eng.steps + i * ARRIVAL_GAP, p)
            for i, p in enumerate(prompts)
        )
        reqs = []
        t0 = time.perf_counter()
        while pending or not eng.idle:
            while pending and pending[0][0] <= eng.steps:
                r = eng.submit(list(pending.popleft()[1]), WALL_GEN)
                clocks[r.req_id] = time.perf_counter()
                reqs.append(r)
            eng.step()
        eng.drain()                               # stream boundary
        jax.block_until_ready(eng.pool)           # ... and nothing earlier
        dt = time.perf_counter() - t0
        rates[mode].append(sum(len(r.generated) for r in reqs) / dt)
        got = [r.generated for r in reqs]
        if mode in streams:
            assert streams[mode] == got, f"{mode} rep diverged"
        streams[mode] = got

    # interleave the modes within each rep - AND alternate which runs
    # first - so slow host drift and whatever warmth later-in-group runs
    # inherit hit every mode equally instead of biasing one
    for rep in range(reps):
        order = modes if rep % 2 == 0 else modes[::-1]
        for mode, depth in order:
            run_once(mode, depth)

    out = {}
    for mode, depth in modes:
        out[mode] = {
            "tokens_per_s_wall": float(np.median(rates[mode])),
            "p50_ttft_s": float(np.percentile(ttfts[mode], 50)),
            "p99_ttft_s": float(np.percentile(ttfts[mode], 99)),
            "reps": int(reps),
            "pipeline_depth": depth,
        }
    assert streams["async"] == streams["sync"], \
        "async burst diverged from sync (bit-identity broken)"
    # paired ratio per interleaved rep: adjacent runs share whatever the
    # host was doing that second, so the ratio is far more stable than
    # the quotient of two independently-noisy medians
    out["async"]["speedup_vs_sync"] = float(np.median(
        np.asarray(rates["async"]) / np.asarray(rates["sync"])
    ))
    return out


_WALL_CACHE = None


def _wall_metrics():
    global _WALL_CACHE
    if _WALL_CACHE is None:
        _WALL_CACHE = wallclock_metrics()
    return _WALL_CACHE


def _measure_all():
    cfg, bundle, params = _bundle()
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(0, cfg.vocab_size, n)) for n in PROMPTS]
    out = {}
    for name, kw in CONFIGS:
        out[name] = burst_metrics(bundle, params, prompts, **kw)
    # every configuration must produce the same per-request streams -
    # the bit-preservation contract the refactor rests on
    base = out["fcfs_b1"]["generated"]
    for name, m in out.items():
        assert m["generated"] == base, f"{name} diverged from fcfs_b1"
    return out


_CACHE = None


def _metrics():
    global _CACHE
    if _CACHE is None:
        _CACHE = _measure_all()
    return _CACHE


# ---------------------------------------------- speculative decode (PR 9) --

# Repetitive burst: constant-token prompts fall into short greedy cycles
# the n-gram prompt-lookup drafter predicts well - the regime speculation
# exists for.  The token values were picked by scanning for prompts whose
# greedy continuations cycle early; all four fit the batch at step 0, so
# the off/on serves must agree on page BYTES too, not just streams.
SPEC_PROMPT_TOKENS = (15, 16, 10, 25)
SPEC_PROMPT_LEN = 24
SPEC_GEN = 48
SPEC_K = 6
SPEC_CHUNK = 24


def spec_decode_metrics():
    """Serve the repetitive burst with speculation off and on (K=6 n-gram
    drafts, verify-in-one-step), synchronous mode.  Bit-identity - token
    streams AND non-null page bytes - is asserted BEFORE any number is
    recorded; the recorded steps-per-token (engine steps / tokens per
    stream, all four rows decoding in lockstep) is deterministic and
    cross-PR diffable, wall tokens/s is the honest-throughput sidecar.
    Acceptance (enforced by benchmarks/run.py): on-row steps_per_token
    <= 0.6."""
    cfg, bundle, params = _bundle()
    prompts = [[t] * SPEC_PROMPT_LEN for t in SPEC_PROMPT_TOKENS]
    total = SPEC_PROMPT_LEN + SPEC_GEN + SPEC_CHUNK
    out = {}
    streams = {}
    pools = {}
    for mode, k in (("off", 0), ("on", SPEC_K)):
        eng = ServeEngine(
            bundle, params, max_batch=BATCH, num_pages=48, page_size=PAGE,
            max_seq_len=total, prefill_chunk=SPEC_CHUNK, speculate=k,
        )
        # warm every jitted call (prefill, decode, and the widened verify)
        eng.submit(list(prompts[0][:4]), 4)
        eng.run_to_completion()
        s0 = eng.steps
        reqs = [eng.submit(list(p), SPEC_GEN) for p in prompts]
        t0 = time.perf_counter()
        eng.run_to_completion()
        dt = time.perf_counter() - t0
        steps = eng.steps - s0
        streams[mode] = [r.generated for r in reqs]
        pools[mode] = {n: np.asarray(v) for n, v in eng.pool.items()}
        st = eng.stats()
        out[mode] = {
            "steps": steps,
            "steps_per_token": steps / SPEC_GEN,
            "tokens_per_s_wall": sum(
                len(r.generated) for r in reqs
            ) / dt,
            "speculate": k,
            "spec": st["spec"],
        }
    assert streams["on"] == streams["off"], \
        "speculative burst diverged from the plain serve (bits broken)"
    for name in pools["off"]:       # page 0 = shared masked-lane sink
        assert np.array_equal(
            pools["off"][name][:, 1:], pools["on"][name][:, 1:]
        ), f"speculation changed page bytes in pool leaf {name!r}"
    out["bit_identical"] = True
    sp = out["on"]["spec"]
    out["on"]["accept_rate"] = sp["accepted"] / max(sp["proposed"], 1)
    return out


_SPEC_CACHE = None


def _spec_metrics():
    global _SPEC_CACHE
    if _SPEC_CACHE is None:
        _SPEC_CACHE = spec_decode_metrics()
    return _SPEC_CACHE


# ------------------------------------------------ noisy-neighbor (PR 8) --

# A flooding tenant (long prompts, throughput class, arrives first) vs a
# small latency-class tenant arriving into the flood.  Step counts are
# deterministic, so the isolation claim diffs exactly across PRs.
FLOOD_PROMPTS = (96,) * 6            # 13 pages each at PAGE=8, GEN=4
VICTIM_PROMPTS = (32, 32, 32)
VICTIM_ARRIVALS = (2, 4, 6)          # engine steps (floods arrive 0..5)
# flood quota: at most 2 concurrent floods (2 x 13 = 26 pages) so slots
# stay free for the latency tenant, and one 32-token chunk per step
# across the whole flood
FLOOD_QUOTA = TenantQuota(max_pages=26, max_step_tokens=CHUNK)


def tenant_isolation_metrics():
    """Serve the victim tenant alone (`isolated`), then into the flood
    under tenant-blind FCFS (`blind`) and under ``TenantQuotaPolicy``
    with the flood quota'd (`tenant`).  The acceptance claim: the tenant
    policy keeps the victim's p99 TTFT within 10% of its isolated serve,
    while blind FCFS queues it behind the flood.  Victim AND flood
    streams are asserted bit-identical between blind and tenant rows
    (quotas are latency-only)."""
    cfg, bundle, params = _bundle()
    rng = np.random.default_rng(2)
    flood = [list(rng.integers(0, cfg.vocab_size, n)) for n in FLOOD_PROMPTS]
    victim = [
        list(rng.integers(0, cfg.vocab_size, n)) for n in VICTIM_PROMPTS
    ]
    total = max(len(p) for p in flood) + GEN
    num_pages = 1 + sum(
        math.ceil((len(p) + GEN) / PAGE) for p in flood + victim
    )

    def serve(mode):
        if mode == "tenant":
            sched = TenantQuotaPolicy({"flood": FLOOD_QUOTA})
        else:
            sched = "fcfs"
        eng = ServeEngine(
            bundle, params, max_batch=BATCH, num_pages=num_pages,
            page_size=PAGE, max_seq_len=total, prefill_chunk=CHUNK,
            scheduler=sched,
        )
        eng.submit(list(flood[0][:2]), 2)
        eng.run_to_completion()                    # warm the jitted calls
        s0 = eng.steps
        pending = []
        if mode != "isolated":
            pending += [
                (s0 + i * ARRIVAL_GAP, p, "flood", "throughput")
                for i, p in enumerate(flood)
            ]
        pending += [
            (s0 + at, p, "interactive", "latency")
            for at, p in zip(VICTIM_ARRIVALS, victim)
        ]
        pending.sort(key=lambda e: e[0])
        pending = deque(pending)
        vic, fld = [], []
        while pending or not eng.idle:
            while pending and pending[0][0] <= eng.steps:
                _, p, tenant, prio = pending.popleft()
                r = eng.submit(list(p), GEN, tenant=tenant, priority=prio)
                (vic if tenant == "interactive" else fld).append(r)
            eng.step()
        ttft = [r.first_token_step - r.submit_step + 1 for r in vic]
        return {
            "victim_mean_ttft_steps": float(np.mean(ttft)),
            "victim_p99_ttft_steps": int(np.max(ttft)),
            "drain_steps": eng.steps - s0,
            "preemptions": eng.preemptions,
            "victim_streams": [r.generated for r in vic],
            "flood_streams": [r.generated for r in fld],
        }

    out = {m: serve(m) for m in ("isolated", "blind", "tenant")}
    # quotas and classes move latency only - never bits
    assert out["tenant"]["victim_streams"] == out["blind"]["victim_streams"]
    assert out["tenant"]["flood_streams"] == out["blind"]["flood_streams"]
    iso = out["isolated"]["victim_p99_ttft_steps"]
    prot = out["tenant"]["victim_p99_ttft_steps"]
    assert prot <= 1.1 * iso, (
        f"tenant policy failed to protect the latency tenant: p99 TTFT "
        f"{prot} steps vs {iso} isolated"
    )
    for m in out.values():
        del m["victim_streams"], m["flood_streams"]
    out["p99_protected_within_10pct"] = True
    return out


_TENANT_CACHE = None


def _tenant_metrics():
    global _TENANT_CACHE
    if _TENANT_CACHE is None:
        _TENANT_CACHE = tenant_isolation_metrics()
    return _TENANT_CACHE


# -------------------------------------------- prefix affinity x replicas --

AFFINITY_MESH = (2, 1)               # 2 data replicas, unsharded pools
AFFINITY_SYSTEM = 64                 # shared system-prompt tokens
AFFINITY_TAIL = 9                    # unique per-request tail
AFFINITY_BURST = 4


def _affinity_main():
    """Subprocess body (2 forced host devices): a shared-system-prompt
    burst through a 2-replica group, prefix-affinity vs blind rotation.
    Streams asserted identical across routing modes (request ids are
    group-global); JSON metrics on stdout."""
    from repro.launch.mesh import make_mesh
    from repro.runtime import EngineReplicaGroup

    cfg, bundle, params = _bundle()
    rng = np.random.default_rng(3)
    system = list(rng.integers(0, cfg.vocab_size, AFFINITY_SYSTEM))
    prompts = [
        system + list(rng.integers(0, cfg.vocab_size, AFFINITY_TAIL))
        for _ in range(1 + AFFINITY_BURST)
    ]
    total = AFFINITY_SYSTEM + AFFINITY_TAIL + GEN
    per_replica = 1 + (1 + AFFINITY_BURST) * math.ceil(total / PAGE)
    mesh = make_mesh(AFFINITY_MESH, ("data", "model"))
    kw = dict(
        max_batch=BATCH, num_pages=per_replica, page_size=PAGE,
        max_seq_len=total, prefill_chunk=CHUNK, prefix_cache=True,
    )

    out = {}
    streams = {}
    for routing in ("affinity", "rr"):
        grp = EngineReplicaGroup(bundle, params, mesh, routing=routing, **kw)
        # warm phase: one request serves (and donates) the system prefix
        r0 = grp.submit(prompts[0], GEN)
        grp.run_to_completion()
        s0 = max(e.steps for e in grp.engines)
        burst = [grp.submit(p, GEN) for p in prompts[1:]]
        grp.run_to_completion()
        ttft = [r.first_token_step - r.submit_step + 1 for r in burst]
        pc = [e.prefix_cache.stats() for e in grp.engines]
        hits = sum(s["hits"] for s in pc)
        misses = sum(s["misses"] for s in pc)
        out[routing] = {
            "mean_ttft_steps": float(np.mean(ttft)),
            "max_ttft_steps": int(np.max(ttft)),
            "drain_steps": int(max(e.steps for e in grp.engines) - s0),
            "cache_hit_rate": hits / max(hits + misses, 1),
            "burst_on_warm_replica": int(sum(
                1 for r in burst
                if grp._owner[r.req_id] is grp._owner[r0.req_id]
            )),
        }
        streams[routing] = [r.generated for r in [r0] + burst]
    assert streams["affinity"] == streams["rr"], \
        "routing changed token streams (must be placement-only)"
    out["burst_size"] = AFFINITY_BURST
    out["system_tokens"] = AFFINITY_SYSTEM
    print(json.dumps(out))


_AFFINITY_CACHE = None


def affinity_metrics():
    """Run :func:`_affinity_main` on 2 forced host devices (cached)."""
    global _AFFINITY_CACHE
    if _AFFINITY_CACHE is None:
        _AFFINITY_CACHE = _run_on_host_devices("--affinity", 2)
    return _AFFINITY_CACHE


def _run_on_host_devices(flag: str, n_devices: int) -> dict:
    """Run one phase of this module in a child interpreter with
    ``n_devices`` forced CPU host devices and return its JSON line.

    These phases exist to exercise multi-device placement on a host with
    one CPU: XLA pins the host device count at backend start, hence the
    child.  The child runs on the CPU backend; the parent refuses to start
    it unless the parent's own backend is the CPU, so a run on an
    accelerator never reports CPU numbers as its own (and no child ever
    competes for a chip the parent holds).  A failed child raises."""
    backend = jax.default_backend()
    if backend != "cpu":
        raise RuntimeError(
            f"scheduler_burst {flag} runs on forced CPU host devices in a "
            f"child process; refusing on the {backend!r} backend"
        )
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS=f"--xla_force_host_platform_device_count={n_devices}",
        PYTHONPATH=os.pathsep.join(
            [os.path.join(os.path.dirname(__file__), "..", "src"),
             os.path.join(os.path.dirname(__file__), "..")]
            + os.environ.get("PYTHONPATH", "").split(os.pathsep)
        ),
    )
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.scheduler_burst", flag],
        env=env, capture_output=True, text=True, timeout=1800,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"scheduler_burst {flag} child failed (rc {proc.returncode}):\n"
            + proc.stderr[-2000:]
        )
    return json.loads(proc.stdout.strip().splitlines()[-1])


# --------------------------------------------------- multi-device burst --

MULTIDEV_MESH = (2, 4)               # (data replicas, model pool shards)


def _multidev_main():
    """Subprocess body (runs with 8 forced host devices): the staggered
    burst on a 2x4 mesh vs 1 device, bit-equality asserted, JSON metrics
    on stdout."""
    import dataclasses

    from repro.configs import get_config
    from repro.launch.mesh import make_mesh
    from repro.models.model_zoo import build
    from repro.runtime import (
        EngineReplicaGroup, ServeEngine, paged_bytes, paged_bytes_per_device,
    )

    n_data, n_model = MULTIDEV_MESH
    cfg = get_config("qwen2-7b").reduced()
    # the reduced() preset caps kv heads at 2; the sharding row needs a
    # model-axis-divisible head count (4 kv heads over model=4)
    cfg = dataclasses.replace(cfg, n_heads=8, n_kv_heads=n_model)
    bundle = build(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(0, cfg.vocab_size, n)) for n in PROMPTS]
    total = max(len(p) for p in prompts) + GEN
    per_replica = math.ceil(len(prompts) / n_data)
    num_pages = 1 + per_replica * math.ceil(total / PAGE)
    kw = dict(
        max_batch=BATCH, num_pages=num_pages, page_size=PAGE,
        max_seq_len=total, prefill_chunk=CHUNK,
    )

    def burst(eng):
        pending = deque(
            (eng_steps0 + i * ARRIVAL_GAP, p)
            for i, p in enumerate(prompts)
        )
        reqs = []
        while pending or not eng.idle:
            now = max(
                e.steps for e in getattr(eng, "engines", [eng])
            )
            while pending and pending[0][0] <= now:
                reqs.append(eng.submit(list(pending.popleft()[1]), GEN))
            eng.step()
        return reqs

    eng_steps0 = 0
    single = ServeEngine(bundle, params, **kw)
    ref = [r.generated for r in burst(single)]

    mesh = make_mesh(MULTIDEV_MESH, ("data", "model"))
    grp = EngineReplicaGroup(bundle, params, mesh, **kw)
    reqs = burst(grp)
    got = [r.generated for r in reqs]
    assert got == ref, "sharded burst diverged from the 1-device serve"

    # PR 6: same burst with every replica pipelined (one step in flight);
    # overlap must not change the sharded streams either
    grp_async = EngineReplicaGroup(
        bundle, params, mesh, pipeline_depth=1, **kw,
    )
    got_async = [r.generated for r in burst(grp_async)]
    assert got_async == ref, \
        "async sharded burst diverged from the 1-device serve"

    ttfts = [r.first_token_step - r.submit_step + 1 for r in reqs]
    pool = grp.engines[0].pool
    print(json.dumps({
        "mean_ttft_steps": float(np.mean(ttfts)),
        "max_ttft_steps": int(np.max(ttfts)),
        "drain_steps": int(max(e.steps for e in grp.engines)),
        "replicas": n_data,
        "model_shards": n_model,
        "pool_bytes_per_replica": paged_bytes(pool),
        "pool_bytes_per_device": paged_bytes_per_device(pool),
        "bit_identical_to_1dev": True,
        "async_bit_identical": True,
    }))


_MULTIDEV_CACHE = None


def multidev_metrics():
    """Run :func:`_multidev_main` on 8 forced host devices (cached)."""
    global _MULTIDEV_CACHE
    if _MULTIDEV_CACHE is None:
        _MULTIDEV_CACHE = _run_on_host_devices("--multidev", 8)
    return _MULTIDEV_CACHE


def report():
    """CSV rows for benchmarks/run.py."""
    rows = []
    base = None
    for name, _ in CONFIGS:
        m = _metrics()[name]
        if base is None:
            base = m["mean_ttft_steps"]
        rows.append((
            f"scheduler_burst_{name}", 0.0,
            f"mean TTFT {m['mean_ttft_steps']:.1f} steps "
            f"(worst {m['max_ttft_steps']}) | drain {m['drain_steps']} "
            f"steps | {m['tokens_per_s']:.0f} tok/s | "
            f"{base / m['mean_ttft_steps']:.2f}x vs fcfs_b1",
        ))
    wall = _wall_metrics()
    for mode in ("sync", "async"):
        m = wall[mode]
        extra = (f" | {m['speedup_vs_sync']:.2f}x vs sync"
                 if mode == "async" else "")
        rows.append((
            f"scheduler_burst_wallclock_{mode}", 0.0,
            f"{m['tokens_per_s_wall']:.0f} tok/s wall | "
            f"TTFT p50 {m['p50_ttft_s'] * 1e3:.1f} ms "
            f"p99 {m['p99_ttft_s'] * 1e3:.1f} ms | "
            f"pipeline_depth={m['pipeline_depth']} | streams bit-identical"
            f"{extra}",
        ))
    sd = _spec_metrics()
    for mode in ("off", "on"):
        m = sd[mode]
        extra = ""
        if mode == "on":
            extra = (f" | k={m['speculate']} ngram, accept rate "
                     f"{m['accept_rate']:.2f}, "
                     f"{m['spec']['rollbacks']} rollbacks | "
                     f"{sd['off']['steps'] / m['steps']:.2f}x fewer steps")
        rows.append((
            f"scheduler_burst_spec_decode_{mode}", 0.0,
            f"{m['steps']} steps for {SPEC_GEN} tok/stream "
            f"({m['steps_per_token']:.3f} steps/token) | "
            f"{m['tokens_per_s_wall']:.0f} tok/s wall | "
            f"streams+pages bit-identical{extra}",
        ))
    ti = _tenant_metrics()
    rows.append((
        "scheduler_burst_tenant_isolation", 0.0,
        f"victim p99 TTFT {ti['tenant']['victim_p99_ttft_steps']} steps "
        f"quota'd (isolated {ti['isolated']['victim_p99_ttft_steps']}, "
        f"blind fcfs {ti['blind']['victim_p99_ttft_steps']}) | "
        f"flood throttled by quota | streams bit-identical blind vs tenant",
    ))
    af = affinity_metrics()
    rows.append((
        "scheduler_burst_prefix_affinity_2rep", 0.0,
        f"affinity: mean TTFT {af['affinity']['mean_ttft_steps']:.1f} "
        f"steps, hit rate {af['affinity']['cache_hit_rate']:.2f}, "
        f"{af['affinity']['burst_on_warm_replica']}/{af['burst_size']} "
        f"on the warm replica | rr: "
        f"{af['rr']['mean_ttft_steps']:.1f} steps, hit rate "
        f"{af['rr']['cache_hit_rate']:.2f} | streams identical",
    ))
    md = multidev_metrics()
    ratio = md["pool_bytes_per_replica"] / md["pool_bytes_per_device"]
    rows.append((
        "scheduler_burst_multidev_2x4", 0.0,
        f"mean TTFT {md['mean_ttft_steps']:.1f} steps "
        f"(worst {md['max_ttft_steps']}) | "
        f"{md['replicas']} replicas x model={md['model_shards']} | "
        f"per-device pool {md['pool_bytes_per_device'] / 1e3:.1f} kB = "
        f"1/{ratio:.1f} of the replica pool | streams bit-identical "
        "to the 1-device serve",
    ))
    return rows


def serving_rows():
    """Machine-readable latency trajectory (benchmarks/BENCH_serving.json).

    Two kinds of rows: deterministic step-count metrics (exact cross-PR
    diffs) plus the wall-clock sync/async pair - real seconds, so those
    two rows vary run to run; what IS stable in them is the invariant
    they certify (streams bit-identical across modes, asserted before
    the numbers are recorded)."""
    out = []
    for name, kw in CONFIGS:
        m = _metrics()[name]
        out.append({
            "name": f"scheduler_burst/{name}",
            "scheduler": kw.get("scheduler"),
            "prefill_batch": kw.get("prefill_batch", BATCH),
            "step_token_budget": kw.get("step_token_budget"),
            "mean_ttft_steps": m["mean_ttft_steps"],
            "max_ttft_steps": m["max_ttft_steps"],
            "drain_steps": m["drain_steps"],
            "workload": {
                "prompts": list(PROMPTS), "gen": GEN, "page": PAGE,
                "chunk": CHUNK, "batch": BATCH,
                "arrival_gap": ARRIVAL_GAP,
            },
        })
    wall = _wall_metrics()
    for mode in ("sync", "async"):
        m = wall[mode]
        row = {
            "name": f"scheduler_burst/wallclock_{mode}",
            "pipeline_depth": m["pipeline_depth"],
            "tokens_per_s_wall": m["tokens_per_s_wall"],
            "p50_ttft_s": m["p50_ttft_s"],
            "p99_ttft_s": m["p99_ttft_s"],
            "reps": m["reps"],
            "bit_identical_to_sync": True,
            "workload": {
                "prompts": list(WALL_PROMPTS), "gen": WALL_GEN,
                "page": PAGE, "chunk": CHUNK, "batch": BATCH,
                "arrival_gap": ARRIVAL_GAP,
            },
        }
        if mode == "async":
            row["speedup_vs_sync"] = m["speedup_vs_sync"]
        out.append(row)
    sd = _spec_metrics()
    for mode in ("off", "on"):
        m = sd[mode]
        row = {
            "name": f"scheduler_burst/spec_decode_{mode}",
            "speculate": m["speculate"],
            "draft": "ngram" if mode == "on" else None,
            "steps": m["steps"],
            "steps_per_token": m["steps_per_token"],
            "tokens_per_s_wall": m["tokens_per_s_wall"],
            "spec": m["spec"],
            "bit_identical": sd["bit_identical"],
            "workload": {
                "prompt_tokens": list(SPEC_PROMPT_TOKENS),
                "prompt_len": SPEC_PROMPT_LEN, "gen": SPEC_GEN,
                "page": PAGE, "chunk": SPEC_CHUNK, "batch": BATCH,
            },
        }
        if mode == "on":
            row["accept_rate"] = m["accept_rate"]
        out.append(row)
    ti = _tenant_metrics()
    out.append({
        "name": "scheduler_burst/tenant_isolation",
        "isolated": ti["isolated"],
        "blind": ti["blind"],
        "tenant": ti["tenant"],
        "p99_protected_within_10pct": ti["p99_protected_within_10pct"],
        "flood_quota": {
            "max_pages": FLOOD_QUOTA.max_pages,
            "max_step_tokens": FLOOD_QUOTA.max_step_tokens,
        },
        "workload": {
            "flood_prompts": list(FLOOD_PROMPTS),
            "victim_prompts": list(VICTIM_PROMPTS),
            "victim_arrivals": list(VICTIM_ARRIVALS),
            "gen": GEN, "page": PAGE, "chunk": CHUNK, "batch": BATCH,
        },
    })
    af = affinity_metrics()
    out.append({
        "name": "scheduler_burst/prefix_affinity_2rep",
        "mesh": {"data": AFFINITY_MESH[0], "model": AFFINITY_MESH[1]},
        "affinity": af["affinity"],
        "rr": af["rr"],
        "streams_identical_across_routing": True,
        "workload": {
            "system_tokens": af["system_tokens"],
            "tail_tokens": AFFINITY_TAIL,
            "burst": af["burst_size"], "gen": GEN, "page": PAGE,
            "chunk": CHUNK, "batch": BATCH,
        },
    })
    md = multidev_metrics()
    out.append({
        "name": "scheduler_burst/multidev_2x4",
        "mesh": {"data": md["replicas"], "model": md["model_shards"]},
        "mean_ttft_steps": md["mean_ttft_steps"],
        "max_ttft_steps": md["max_ttft_steps"],
        "drain_steps": md["drain_steps"],
        "pool_bytes_per_replica": md["pool_bytes_per_replica"],
        "pool_bytes_per_device": md["pool_bytes_per_device"],
        "bit_identical_to_1dev": md["bit_identical_to_1dev"],
        "async_bit_identical": md.get("async_bit_identical", False),
        "workload": {
            "prompts": list(PROMPTS), "gen": GEN, "page": PAGE,
            "chunk": CHUNK, "batch": BATCH,
            "arrival_gap": ARRIVAL_GAP,
        },
    })
    return out


if __name__ == "__main__":
    if "--multidev" in sys.argv:
        _multidev_main()
    elif "--affinity" in sys.argv:
        _affinity_main()
    else:
        for name, us, derived in report():
            print(f"{name},{us:.1f},{derived}")
