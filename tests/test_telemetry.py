"""Serving-stack observability (PR 7): bit-neutrality, metrics math,
stats schema, TTFT consolidation, and the online numerics probe.

The load-bearing contract: telemetry FULLY ON (tracing + metrics +
numerics probe at sample interval 1) vs FULLY OFF produces bit-identical
token streams AND page bytes, across sync/async pipeline modes and raw/
quantized pool dtypes - instrumentation observes the serve, it never
participates in it.  (The sharded topologies are pinned in
tests/test_sharded_serving.py, which needs the multidevice launcher.)

Also here: exact unit tests for the dependency-free metrics registry
(histogram bucket/percentile math, ring-buffer overflow, cross-replica
aggregation), the versioned ``stats()`` schema shared by ServeEngine and
EngineReplicaGroup, the retirement-side TTFT stamp (single site, original
-submit semantics across preempt/resume), trace export formats, and the
numerics probe flagging the paper's overflow drivers on the adversarial
generators (resonance -> negative fp16 margin; sequence bias -> large
PASA shift magnitude)."""

import ast
import inspect
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import adversarial_inputs as adv

from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models.model_zoo import build
from repro.runtime import (
    STATS_SCHEMA,
    EngineReplicaGroup,
    Histogram,
    MetricsRegistry,
    NumericsProbe,
    ServeEngine,
    StepTracer,
    Telemetry,
    aggregate_snapshots,
)
from repro.runtime.telemetry import (
    ANNOTATION_PREFIX,
    CHILD_SPANS,
    STEP_SPANS,
    WORK_COUNTERS,
)

GEN = 4
PROMPT_LENS = (37, 21, 45, 12)


@pytest.fixture(scope="module")
def tiny_bundle():
    cfg = get_config("qwen3-4b").reduced()
    bundle = build(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    return bundle, params


@pytest.fixture(scope="module")
def prompts(tiny_bundle):
    rng = np.random.default_rng(0)
    vocab = tiny_bundle[0].cfg.vocab_size
    return [list(rng.integers(0, vocab, n)) for n in PROMPT_LENS]


def _serve(bundle, params, prompts, telemetry=None, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("num_pages", 40)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("prefill_chunk", 16)
    eng = ServeEngine(bundle, params, telemetry=telemetry, **kw)
    reqs = [eng.submit(p, GEN) for p in prompts]
    eng.run_to_completion()
    return reqs, eng


def _full_telemetry(**kw):
    """Every layer on, probe at the most aggressive cadence."""
    kw.setdefault("numerics_every", 1)
    return Telemetry(tracing=True, metrics=True, **kw)


def _assert_pools_bit_equal(pool_a, pool_b):
    assert set(pool_a) == set(pool_b)
    for name in pool_a:
        a, b = np.asarray(pool_a[name]), np.asarray(pool_b[name])
        np.testing.assert_array_equal(a[:, 1:], b[:, 1:], err_msg=name)


# ------------------------------------------------------ bit-neutrality --

@pytest.mark.parametrize("depth", [0, 1], ids=["sync", "async"])
@pytest.mark.parametrize("dtype", ["bf16", "int8"])
def test_telemetry_is_bit_neutral(tiny_bundle, prompts, dtype, depth):
    """THE observability contract: tracing + metrics + per-step numerics
    probe change NOTHING - token streams, first-token stamps, and every
    physical page byte (sidecars included) match the uninstrumented
    serve, in both pipeline modes, raw and quantized pools."""
    bundle, params = tiny_bundle
    kw = dict(cache_dtype=dtype, pipeline_depth=depth, prefix_cache=True)
    ref, ref_eng = _serve(bundle, params, prompts, **kw)
    tel = _full_telemetry()
    got, eng = _serve(bundle, params, prompts, telemetry=tel, **kw)
    assert [r.generated for r in got] == [r.generated for r in ref]
    assert ([r.first_token_step for r in got]
            == [r.first_token_step for r in ref])
    _assert_pools_bit_equal(ref_eng.pool, eng.pool)
    # and the instrumentation actually observed the serve
    snap = tel.metrics_snapshot()
    assert snap["counters"]["serve.requests_finished"]["value"] == len(
        prompts
    )
    assert snap["counters"]["numerics.samples"]["value"] > 0
    assert snap["gauges"]["numerics.fp16_margin"]["value"] is not None
    assert snap["histograms"]["serve.ttft_steps"]["count"] == len(prompts)
    assert tel.tracer.emitted > 0


def test_telemetry_bit_neutral_under_preempt_and_cancel(tiny_bundle,
                                                        prompts):
    """The drain-heavy paths (preemption's drain-and-replan, mid-flight
    cancel) with full telemetry: streams still match the uninstrumented
    serve, and the lifecycle counters see the events."""
    bundle, params = tiny_bundle

    def run(tel):
        eng = ServeEngine(
            bundle, params, max_batch=2, num_pages=12, page_size=8,
            max_seq_len=64, prefill_chunk=16, prefix_cache=True,
            preemption=True, preempt_patience=2, pipeline_depth=1,
            telemetry=tel,
        )
        ra = eng.submit(prompts[2], 12)          # long straggler
        for _ in range(3):
            eng.step()
        rb = eng.submit(prompts[0], GEN)         # forces a preemption
        rc = eng.submit(prompts[1], GEN)
        eng.step()
        assert eng.cancel(rc.req_id)             # mid-serve cancel
        eng.run_to_completion()
        return (ra, rb), eng

    (ra0, rb0), eng0 = run(None)
    tel = _full_telemetry()
    (ra1, rb1), eng1 = run(tel)
    assert eng0.preemptions >= 1, "scenario must actually preempt"
    assert eng1.preemptions == eng0.preemptions
    assert ra1.generated == ra0.generated
    assert rb1.generated == rb0.generated
    snap = tel.metrics_snapshot()
    assert snap["counters"]["serve.preemptions"]["value"] >= 1
    assert snap["counters"]["serve.requests_cancelled"]["value"] == 1
    assert snap["counters"]["serve.resumes"]["value"] >= 1
    kinds = {e.name for e in tel.tracer.events()}
    assert {"preempt", "resume", "cancel"} <= kinds


def test_spec_telemetry_bit_neutral_and_lazy(tiny_bundle):
    """Speculative-decoding telemetry (PR 9): the serve.spec.* counters
    and the accepted_per_verify histogram are (a) BIT-NEUTRAL - the
    instrumented speculative serve matches the uninstrumented one stream
    for stream and byte for byte, (b) exact mirrors of the engine's own
    tallies, and (c) LAZILY registered - a serve that never speculates
    keeps the pinned default catalog free of spec instruments."""
    bundle, params = tiny_bundle
    spec_prompts = [[3, 5, 7, 9] * 4 + [3], [11, 12, 13] * 5]
    kw = dict(speculate=3, cache_dtype="int8")
    ref, ref_eng = _serve(bundle, params, spec_prompts, **kw)
    tel = _full_telemetry()
    got, eng = _serve(bundle, params, spec_prompts, telemetry=tel, **kw)
    assert [r.generated for r in got] == [r.generated for r in ref]
    _assert_pools_bit_equal(ref_eng.pool, eng.pool)

    st = eng.stats()["spec"]
    assert st["verify_steps"] >= 1, "workload must actually speculate"
    snap = tel.metrics_snapshot()
    c = snap["counters"]
    assert c["serve.spec.proposed"]["value"] == st["proposed"]
    assert c["serve.spec.accepted"]["value"] == st["accepted"]
    assert c["serve.spec.verify_steps"]["value"] == st["verify_steps"]
    assert c["serve.spec.rollback_pages"]["value"] >= 0
    h = snap["histograms"]["serve.spec.accepted_per_verify"]
    assert h["count"] == st["verify_steps"]    # one observation per row
    assert h["sum"] == st["accepted"]

    # lazy registration: no speculation -> no spec instruments
    tel_off = _full_telemetry()
    _serve(bundle, params, spec_prompts, telemetry=tel_off)
    snap_off = tel_off.metrics_snapshot()
    assert not any(k.startswith("serve.spec.") for k in
                   list(snap_off["counters"]) + list(snap_off["histograms"]))


# -------------------------------------------------------- metrics math --

def test_histogram_exact_aggregates_and_percentiles():
    h = Histogram("t", bounds=(1.0, 2.0, 4.0, 8.0))
    for v in (0.5, 1.5, 1.5, 3.0, 7.0):
        h.observe(v)
    assert h.count == 5 and h.sum == pytest.approx(13.5)
    assert h.min == 0.5 and h.max == 7.0
    assert [c for _, c in zip(h.bounds, h.counts)] == [1, 2, 1, 1]
    # p50: rank 2.5 falls in the (1, 2] bucket (cumulative 2 -> 4)
    p50 = h.percentile(50)
    assert 1.0 <= p50 <= 2.0
    # exact extremes beat interpolation at the edges
    assert h.percentile(0) == 0.5
    assert h.percentile(100) == 7.0
    assert h.percentile(99) <= 7.0
    with pytest.raises(ValueError):
        h.percentile(101)


def test_histogram_overflow_bucket_is_conservative():
    h = Histogram("t", bounds=(1.0, 2.0))
    h.observe(100.0)
    h.observe(200.0)
    assert h.counts[-1] == 2
    # overflow percentile reports the bucket's lower edge clamped into
    # the observed range - deterministic, never a fabricated interior
    assert h.percentile(50) == 100.0
    snap = h.snapshot()
    assert snap["buckets"][-1] == ["inf", 2]


def test_histogram_empty_and_validation():
    h = Histogram("t", bounds=(1.0, 2.0))
    assert h.percentile(50) is None
    assert h.snapshot()["p99"] is None
    with pytest.raises(ValueError):
        Histogram("bad", bounds=())
    with pytest.raises(ValueError):
        Histogram("bad", bounds=(2.0, 1.0))


def test_registry_kinds_and_validation():
    m = MetricsRegistry()
    c = m.counter("a")
    assert m.counter("a") is c          # idempotent get-or-create
    with pytest.raises(ValueError):
        m.gauge("a")                    # kind conflict fails fast
    with pytest.raises(ValueError):
        c.inc(-1)                       # counters are monotone
    m.gauge("g").set(3)
    m.histogram("h").observe(1.0)
    snap = m.snapshot()
    assert set(snap) == {"counters", "gauges", "histograms"}
    json.dumps(snap)                    # scrape payload is plain JSON


def test_aggregate_snapshots_cross_replica():
    a, b = MetricsRegistry(), MetricsRegistry()
    for m, n in ((a, 3), (b, 5)):
        m.counter("c").inc(n)
        m.gauge("depth").set(n)
        m.gauge("clock_max").set(n)
        h = m.histogram("h", bounds=(1.0, 10.0))
        h.observe(n)
    merged = aggregate_snapshots([a.snapshot(), b.snapshot()])
    assert merged["counters"]["c"]["value"] == 8
    assert merged["gauges"]["depth"]["value"] == 8          # totals sum
    assert merged["gauges"]["clock_max"]["value"] == 5      # *_max maxes
    h = merged["histograms"]["h"]
    assert h["count"] == 2 and h["sum"] == 8.0
    assert h["min"] == 3 and h["max"] == 5
    assert h["p99"] <= 5.0
    # unset gauges don't poison the merge
    c = MetricsRegistry()
    c.gauge("depth")
    merged2 = aggregate_snapshots([a.snapshot(), c.snapshot()])
    assert merged2["gauges"]["depth"]["value"] == 3
    # mismatched bucket bounds are an error, not silent garbage
    d = MetricsRegistry()
    d.histogram("h", bounds=(1.0, 2.0)).observe(1.0)
    with pytest.raises(ValueError):
        aggregate_snapshots([a.snapshot(), d.snapshot()])


# ------------------------------------------------------- ring + export --

def test_ring_buffer_drops_oldest_and_reports_it(tmp_path):
    tr = StepTracer(capacity=8)
    for i in range(20):
        tr.instant("tick", i)
    evs = tr.events()
    assert len(evs) == 8
    assert tr.emitted == 20 and tr.dropped == 12
    assert [e.step for e in evs] == list(range(12, 20))  # oldest dropped
    path = tmp_path / "t.jsonl"
    n = tr.write_jsonl(str(path))
    lines = path.read_text().splitlines()
    assert n == 8 and len(lines) == 9                    # meta + events
    meta = json.loads(lines[0])
    assert meta["dropped"] == 12 and meta["capacity"] == 8
    assert json.loads(lines[1])["step"] == 12
    with pytest.raises(ValueError):
        StepTracer(capacity=0)


def test_chrome_trace_export_shape(tmp_path):
    tr = StepTracer()
    tr.span("plan", 0, 0.0, 0.001, args={"live": 2})
    tr.span("dispatch", 0, 0.001, 0.003, engine=1)
    tr.instant("submit", 0, args={"req_id": 7})
    tr.counter("engine", 0, {"waiting": 3})
    path = tmp_path / "trace.json"
    n = tr.write_chrome_trace(str(path))
    doc = json.loads(path.read_text())
    evs = doc["traceEvents"]
    assert n == 4
    phases = {e["ph"] for e in evs}
    assert {"X", "i", "C", "M"} <= phases
    span = next(e for e in evs if e["ph"] == "X" and e["name"] == "plan")
    assert span["dur"] == pytest.approx(1000.0)          # microseconds
    assert span["args"]["step"] == 0
    inst = next(e for e in evs if e["ph"] == "i")
    assert inst["args"]["req_id"] == 7
    pids = {e["pid"] for e in evs if e["ph"] != "M"}
    assert pids == {0, 1}                                # engine -> pid
    names = {
        (e["pid"], e["args"]["name"])
        for e in evs if e.get("name") == "process_name"
    }
    assert names == {(0, "engine 0"), (1, "engine 1")}


def test_serve_trace_contains_lifecycle_and_spans(tiny_bundle, prompts):
    bundle, params = tiny_bundle
    tel = Telemetry(tracing=True, metrics=False, numerics_every=0)
    reqs, eng = _serve(bundle, params, prompts, telemetry=tel,
                       pipeline_depth=1)
    by_name = {}
    for e in tel.tracer.events():
        by_name.setdefault(e.name, []).append(e)
    assert len(by_name["submit"]) == len(prompts)
    assert len(by_name["admit"]) == len(prompts)
    assert len(by_name["first_token"]) == len(prompts)
    assert len(by_name["finish"]) == len(prompts)
    assert {e.args["req_id"] for e in by_name["first_token"]} == {
        r.req_id for r in reqs
    }
    # the trace's first_token stamps ARE the Request bookkeeping
    stamp = {e.args["req_id"]: e.step for e in by_name["first_token"]}
    assert stamp == {r.req_id: r.first_token_step for r in reqs}
    assert len(by_name["plan"]) == eng.steps
    assert by_name["dispatch"], "dispatched steps must emit spans"
    assert len(by_name["retire"]) == eng.steps
    for e in by_name["plan"]:
        assert e.dur >= 0.0 and e.kind == "span"


def test_engine_counter_counts_dispatched_work(tiny_bundle):
    """The per-step ``engine`` counter carries the work each step
    dispatched, worked out by hand: max_batch 2 x 4 pages x page 8 = 64
    K/V positions gathered per decode call, a 2 x 16 prefill grid.
      step 0: one prefill call, A's first chunk (16) and B's whole prompt
              (5); B's prompt is done, so B decodes at position 5 (6 live);
      step 1: A's last 4 prompt tokens; A decodes at 20 (21 live);
      step 2: A decodes at 21 (22 live) and finishes."""
    bundle, params = tiny_bundle
    tel = Telemetry(tracing=True, metrics=False)
    eng = ServeEngine(
        bundle, params, max_batch=2, num_pages=16, page_size=8,
        max_seq_len=32, prefill_chunk=16, pipeline_depth=0, telemetry=tel,
    )
    eng.submit(list(range(1, 21)), 3)
    eng.submit(list(range(1, 6)), 2)
    eng.run_to_completion()
    got = [
        {k: e.args[k] for k in WORK_COUNTERS}
        for e in tel.tracer.events() if e.kind == "counter"
    ]
    row = lambda *v: dict(zip(WORK_COUNTERS, v))  # noqa: E731
    assert got == [
        row(1, 6, 64, 21, 32),
        row(1, 21, 64, 4, 32),
        row(1, 22, 64, 0, 0),
    ]
    counters = [e for e in tel.tracer.events() if e.kind == "counter"]
    assert set(counters[0].args) == {
        "waiting", "running", "free_pages", "inflight", *WORK_COUNTERS,
    }


def test_engine_counter_counts_live_pages_on_kernel_route(monkeypatch):
    """On the paged decode kernel's route ``kv_slots`` counts the pages
    the kernel reads: each slot's ceil(kv_len / page) pages, kv_len =
    pos + 1, and one null page for a slot not decoding.  The schedule of
    the test above, with page == block_kv == 8 so the kernel applies:
      step 0: B at 5 (1 page) + A not decoding (1)  -> 2 pages = 16;
      step 1: A at 20 (3 pages) + B's empty slot (1) -> 4 pages = 32;
      step 2: A at 21 (3 pages) + the empty slot (1) -> 32."""
    import dataclasses

    from repro.models import attention

    monkeypatch.setattr(
        attention, "_paged_decode_backend", lambda: "interpret"
    )
    cfg = get_config("qwen3-4b").reduced()
    cfg = dataclasses.replace(
        cfg, attention=dataclasses.replace(cfg.attention, block_kv=8)
    )
    bundle = build(cfg)
    tel = Telemetry(tracing=True, metrics=False)
    eng = ServeEngine(
        bundle, bundle.init(jax.random.PRNGKey(0)), max_batch=2,
        num_pages=16, page_size=8, max_seq_len=32, prefill_chunk=16,
        pipeline_depth=0, telemetry=tel,
    )
    eng.submit(list(range(1, 21)), 3)
    eng.submit(list(range(1, 6)), 2)
    eng.run_to_completion()
    got = [
        (e.args["kv_live"], e.args["kv_slots"])
        for e in tel.tracer.events() if e.kind == "counter"
    ]
    assert got == [(6, 16), (21, 32), (22, 32)]


def _profiled_serve(bundle, params, prompts, tmp_path, telemetry):
    """A serve under ``jax.profiler``: the host events named ``serve.*``
    as [name, start_ns, end_ns], and the telemetry's spans."""
    from jax.profiler import ProfileData

    _serve(bundle, params, prompts[:1], telemetry=None)     # compile
    with jax.profiler.trace(str(tmp_path)):
        _serve(bundle, params, prompts, telemetry=telemetry, pipeline_depth=1)
    (path,) = tmp_path.glob("**/*.xplane.pb")
    events = [
        [ev.name, ev.start_ns, ev.start_ns + ev.duration_ns]
        for plane in ProfileData.from_file(str(path)).planes
        if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events
        if ev.name.startswith("serve.")
    ]
    return events


def test_phases_are_profiler_annotations(tiny_bundle, prompts, tmp_path):
    """Each engine phase is one ``with`` block that records its
    StepTracer span AND a ``serve.<span>`` profiler annotation, so a
    profiler trace of the serve shows the phases on the device's clock:
    one annotation per span, children inside their parents."""
    bundle, params = tiny_bundle
    tel = Telemetry(tracing=True, metrics=False)
    events = _profiled_serve(bundle, params, prompts, tmp_path, tel)
    spans = [e for e in tel.tracer.events() if e.kind == "span"]
    count = lambda names: {  # noqa: E731
        n: sum(1 for x in names if x == n) for n in set(names)
    }
    assert count([n for n, _, _ in events]) == count(
        [ANNOTATION_PREFIX + e.name for e in spans]
    )
    assert {e.name for e in spans} == set(STEP_SPANS) | set(CHILD_SPANS)
    by_name = {}
    for n, s, e in events:
        by_name.setdefault(n, []).append((s, e))

    def inside(child, parent):
        return all(
            any(ps <= s and e <= pe for ps, pe in by_name[parent])
            for s, e in by_name[child]
        )

    assert inside("serve.prefill", "serve.dispatch")
    assert inside("serve.decode", "serve.dispatch")
    assert inside("serve.retire.wait", "serve.retire")
    # the step spans tile each step in order
    steps = [e for e in spans if e.name in STEP_SPANS]
    assert [e.name for e in steps[:3]] == ["plan", "dispatch", "retire"]
    # and without telemetry the engine opens no annotation at all
    assert _profiled_serve(bundle, params, prompts, tmp_path / "off",
                           None) == []


# ------------------------------------------------------- stats schema --

ENGINE_STATS_KEYS = frozenset({
    "schema", "steps", "running", "waiting", "finished", "free_pages",
    "live_pages", "cache_bytes", "cache_bytes_per_device", "page_size",
    "pool_dtype", "chunked_prefill", "scheduler", "prefill_batch",
    "step_token_budget", "preemptions", "trimmed_pages", "temperature",
    "last_step_tokens", "max_step_tokens", "pipeline_depth", "inflight",
    "cancellations", "prefix_cache", "speculate", "spec",
})
PREFIX_CACHE_KEYS = frozenset({
    "cached_pages", "evictable_pages", "hits", "misses", "evictions",
    "donations",
})
SPEC_KEYS = frozenset({
    "proposed", "accepted", "rollbacks", "verify_steps",
})


def test_engine_stats_schema_pinned(tiny_bundle, prompts):
    """The versioned schema: exactly these keys, always all present."""
    bundle, params = tiny_bundle
    _, eng = _serve(bundle, params, prompts[:2], prefix_cache=True)
    st = eng.stats()
    assert st["schema"] == STATS_SCHEMA == 2
    assert frozenset(st) == ENGINE_STATS_KEYS
    assert frozenset(st["prefix_cache"]) == PREFIX_CACHE_KEYS
    # the spec sub-dict is always present (zeros when speculation is off)
    assert frozenset(st["spec"]) == SPEC_KEYS
    assert st["speculate"] == 0
    assert all(v == 0 for v in st["spec"].values())
    # prefix_cache is present (None) even when the cache is off
    _, eng_off = _serve(bundle, params, prompts[:1], prefix_cache=False)
    st_off = eng_off.stats()
    assert frozenset(st_off) == ENGINE_STATS_KEYS
    assert st_off["prefix_cache"] is None
    json.dumps(st)                       # snapshot is plain JSON


def test_group_stats_is_true_aggregation(tiny_bundle, prompts):
    """EngineReplicaGroup.stats(): SAME shared keys as the engine (plus
    replicas/engines), tallies summed, clocks maxed, config passed
    through - a 1x1 mesh group runs on one device in-process."""
    bundle, params = tiny_bundle
    mesh = make_mesh((1, 1), ("data", "model"))
    tel = Telemetry(tracing=False, metrics=True, numerics_every=0)
    grp = EngineReplicaGroup(
        bundle, params, mesh, max_batch=4, num_pages=40, page_size=8,
        max_seq_len=64, prefill_chunk=16, prefix_cache=True,
        telemetry=tel,
    )
    reqs = [grp.submit(p, GEN) for p in prompts]
    grp.run_to_completion()
    st = grp.stats()
    assert frozenset(st) == ENGINE_STATS_KEYS | {"replicas", "engines"}
    assert st["schema"] == STATS_SCHEMA
    assert st["replicas"] == 1 and len(st["engines"]) == 1
    per = st["engines"]
    assert all(frozenset(s) == ENGINE_STATS_KEYS for s in per)
    assert st["finished"] == sum(s["finished"] for s in per) == len(reqs)
    assert st["steps"] == max(s["steps"] for s in per)
    assert st["scheduler"] == per[0]["scheduler"]
    assert frozenset(st["prefix_cache"]) == PREFIX_CACHE_KEYS
    # spec tallies aggregate per-key across replicas (all-zero here)
    assert frozenset(st["spec"]) == SPEC_KEYS
    assert st["spec"] == {
        k: sum(s["spec"][k] for s in per) for k in SPEC_KEYS
    }
    assert st["speculate"] == per[0]["speculate"] == 0
    # the aggregated metrics snapshot sees every replica's registry
    snap = grp.metrics_snapshot()
    assert snap["counters"]["serve.requests_finished"]["value"] == len(
        reqs
    )
    assert grp.engines[0].metrics_snapshot() is not None
    # engines without telemetry scrape as None
    grp2 = EngineReplicaGroup(
        bundle, params, mesh, max_batch=2, num_pages=20, page_size=8,
        max_seq_len=64, prefill_chunk=16,
    )
    assert grp2.metrics_snapshot() is None


# ------------------------------------------------------------- TTFT --

def test_first_token_stamped_only_at_retirement():
    """The PR-7 bugfix, pinned statically: ``first_token_step`` is
    assigned in exactly ONE ServeEngine method - ``_retire_one`` - not in
    the two dispatch-side sites the pre-PR-7 engine had (engine.py:1176
    and :1342 of the old layout)."""
    import repro.runtime.engine as engine_mod

    tree = ast.parse(inspect.getsource(engine_mod))
    sites = []
    for cls in ast.walk(tree):
        if not (isinstance(cls, ast.ClassDef) and cls.name == "ServeEngine"):
            continue
        for fn in cls.body:
            if not isinstance(fn, ast.FunctionDef):
                continue
            for node in ast.walk(fn):
                if (isinstance(node, ast.Assign) and any(
                    isinstance(t, ast.Attribute)
                    and t.attr == "first_token_step"
                    for t in node.targets
                )):
                    sites.append(fn.name)
    assert sites == ["_retire_one"], (
        f"first_token_step must have exactly one retirement-side stamp "
        f"site, found assignments in {sites}"
    )


def test_ttft_measured_from_original_submit_across_preemption(
    tiny_bundle, prompts
):
    """A preempted-then-resumed request reports TTFT from its ORIGINAL
    submit/emission, not from re-admission - and the telemetry histogram
    observes each request exactly once with that original value."""
    bundle, params = tiny_bundle
    tel = _full_telemetry(numerics_every=0)
    eng = ServeEngine(
        bundle, params, max_batch=2, num_pages=12, page_size=8,
        max_seq_len=64, prefill_chunk=16, prefix_cache=True,
        preemption=True, preempt_patience=2, telemetry=tel,
    )
    ra = eng.submit(prompts[2], 12)
    for _ in range(3):
        eng.step()
    assert ra.generated, "straggler must be mid-decode before preemption"
    first_stamp = ra.first_token_step
    assert first_stamp >= 0
    rb = eng.submit(prompts[0], GEN)
    eng.run_to_completion()
    assert ra.preempt_count >= 1, "scenario must actually preempt"
    assert ra.first_token_step == first_stamp, (
        "preemption/resume must not restamp the first token"
    )
    assert ra.first_token_step < ra.preempt_step
    h = tel.metrics.histogram("serve.ttft_steps")
    assert h.count == 2                  # one observation per request
    observed = {ra.first_token_step - ra.submit_step + 1,
                rb.first_token_step - rb.submit_step + 1}
    assert h.min in observed and h.max in observed


# ----------------------------------------------------- numerics probe --

def _pages_from_k(k_bshd, page=8):
    """(1, KVH, S, D) adversarial K -> raw pool leaf (1, P, page, KVH*D)
    + the probe's (page id, valid rows) list."""
    _, kvh, s, d = k_bshd.shape
    n = s // page
    pages = np.moveaxis(np.asarray(k_bshd, np.float32)[0], 0, 1)
    pages = pages.reshape(n, page, kvh * d)
    pool = {"k": jnp.asarray(pages)[None]}       # 1 layer
    return pool, [(i, page) for i in range(n)], kvh


def test_probe_flags_resonance_overflow():
    """The acceptance fixture: phase-coincident K at the paper's RES_AMP
    drives the Q-free score-amplitude proxy past FP16_MAX - the probe
    must report a NEGATIVE overflow margin and near-1 resonance."""
    kvh, d, s = 2, 32, 64
    _, k, _ = adv.make_adversarial(
        "resonance_0", jax.random.PRNGKey(0),
        q_shape=(1, kvh, 4, d), kv_shape=(1, kvh, s, d),
    )
    pool, pages_valid, kvh = _pages_from_k(k)
    probe = NumericsProbe(every=1, max_pages=4)
    reading = probe.sample(pool, pages_valid, n_kv_heads=kvh)
    assert reading["score_amp_max"] > 65504.0
    assert reading["fp16_margin"] < 0.0
    assert reading["resonance_max"] > 0.9
    assert reading["pages_sampled"] == 4
    assert probe.samples == 1 and probe.last is reading


def test_probe_seq_bias_shift_magnitude():
    """Sequence-dim bias is exactly what the PASA shift absorbs: the
    per-page shift magnitude gauge must see the ~SEQ_BIAS-scale channel
    means, far above the unit-variance noise floor."""
    kvh, d, s = 2, 32, 64
    _, k_bias, _ = adv.make_adversarial(
        "seq_bias", jax.random.PRNGKey(1),
        q_shape=(1, kvh, 4, d), kv_shape=(1, kvh, s, d),
    )
    k_plain = jax.random.normal(jax.random.PRNGKey(2), (1, kvh, s, d), jnp.float32)
    pool_b, pv, _ = _pages_from_k(k_bias)
    pool_p, _, _ = _pages_from_k(k_plain)
    probe = NumericsProbe(every=1, max_pages=8)
    biased = probe.sample(pool_b, pv, n_kv_heads=kvh)
    plain = probe.sample(pool_p, pv, n_kv_heads=kvh)
    assert biased["shift_mag_max"] > 10.0
    assert biased["shift_mag_max"] > 5 * plain["shift_mag_max"]


def test_probe_masks_stale_tail_rows():
    """Rows past a page's valid length are recycled-page debris by
    design: poisoning them with Inf must not perturb the reading."""
    kvh, d, s, page = 2, 32, 64, 8
    k = jax.random.normal(jax.random.PRNGKey(3), (1, kvh, s, d), jnp.float32)
    pool, pages_valid, _ = _pages_from_k(k)
    clean = NumericsProbe(every=1).sample(
        pool, [(i, 3) for i, _ in pages_valid], n_kv_heads=kvh
    )
    poisoned = {
        "k": pool["k"].at[:, :, 3:].set(jnp.inf)   # debris past valid=3
    }
    dirty = NumericsProbe(every=1).sample(
        poisoned, [(i, 3) for i, _ in pages_valid], n_kv_heads=kvh
    )
    for key in ("kv_max_abs", "score_amp_max", "fp16_margin",
                "shift_mag_max", "resonance_max"):
        assert np.isfinite(dirty[key])
        assert dirty[key] == pytest.approx(clean[key])


def test_probe_empty_and_validation():
    probe = NumericsProbe(every=4)
    assert probe.sample({"k": jnp.zeros((1, 2, 8, 4))}, [],
                        n_kv_heads=1) is None
    assert probe.sample({"k": jnp.zeros((1, 2, 8, 4))}, [(1, 0)],
                        n_kv_heads=1) is None
    assert [probe.due(s) for s in (0, 1, 4, 7, 8)] == [
        True, False, True, False, True
    ]
    with pytest.raises(ValueError):
        NumericsProbe(every=0)
    with pytest.raises(ValueError):
        NumericsProbe(every=1, max_pages=0)


def test_probe_reads_quantized_sidecars_live(tiny_bundle, prompts):
    """On an int8 pool the probe dequantizes codes through the page's
    scale/shift sidecars and reads the shift gauge straight from the
    sidecar - end-to-end on a live serve."""
    bundle, params = tiny_bundle
    tel = _full_telemetry()
    _serve(bundle, params, prompts[:2], telemetry=tel, cache_dtype="int8")
    snap = tel.metrics_snapshot()
    assert snap["counters"]["numerics.samples"]["value"] > 0
    for key in ("numerics.kv_max_abs", "numerics.score_amp_max",
                "numerics.fp16_margin", "numerics.shift_mag_max",
                "numerics.resonance_max"):
        v = snap["gauges"][key]["value"]
        assert v is not None and np.isfinite(v)
    # benign traffic: nowhere near the fp16 ceiling, sane resonance
    assert snap["gauges"]["numerics.fp16_margin"]["value"] > 0
    assert 0.0 <= snap["gauges"]["numerics.resonance_max"]["value"] <= 1.0
    assert snap["counters"]["numerics.fp16_overflow_risk"]["value"] == 0
