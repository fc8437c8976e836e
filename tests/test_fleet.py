"""Multi-tenant fleet scheduling (PR 8): quotas, priority classes, and
prefix-affinity routing.

Three layers:

  * **Policy** (pure host): :class:`TenantQuotaPolicy` unit tests -
    per-tenant page-quota accounting in ``plan_admission`` (sequential
    charging, withholding), latency-before-throughput class ordering,
    the aging guard's starvation freedom, per-tenant ``max_step_tokens``
    caps in ``plan_prefill``, and class-aware victim choice.
  * **Engine** (single device): the PR's hard contract - tenant
    scheduling is LATENCY-ONLY.  For a fixed routing outcome the token
    streams are bit-identical to the tenant-blind FCFS serve across
    sync/async x {bf16, fp8_e4m3, int8} pool dtypes, through
    preempt-resume and cancel under quota pressure.  Quota withholding
    must never trigger preemption (withheld != page-starved).
  * **Routing** (host-side, fake replicas): ``EngineReplicaGroup``
    placement decisions - the least-loaded fallback that closes the
    post-``cancel`` imbalance strict rotation ignored (the PR's
    satellite bugfix), the rotating-cursor tiebreak that keeps the
    pinned ``i::n`` deal under equal loads, and prefix-affinity routing
    from ``RadixPrefixCache.probe_len``.  Real-mesh end-to-end routing
    runs in tests/test_sharded_serving.py (multidevice suite).
"""

import jax
import numpy as np
import pytest

from repro.runtime import (
    DEFAULT_TENANT,
    PRIORITY_CLASSES,
    ROUTING_MODES,
    EngineReplicaGroup,
    PageAllocator,
    RadixPrefixCache,
    RequestView,
    SchedulerPolicy,
    ServeEngine,
    TenantQuota,
    TenantQuotaPolicy,
    chunked_cold_reference,
    get_scheduler,
)


def _trie(page_size, n_pages_cached):
    """A host-only radix trie holding ``n_pages_cached`` pages of the
    prompt ``0, 1, 2, ...`` (allocator-backed, as in the engine)."""
    alloc = PageAllocator(num_pages=16)
    cache = RadixPrefixCache(alloc, page_size=page_size)
    pages = alloc.alloc(n_pages_cached)
    cache.insert(list(range(n_pages_cached * page_size)), pages)
    return cache


def _v(req_id, *, tenant=DEFAULT_TENANT, priority="throughput",
       prompt_len=64, remaining_prefill=None, remaining_decode=8,
       submit_step=0, admit_step=-1, slot=-1, pages_needed=4,
       preempt_count=0, preempt_step=-1):
    return RequestView(
        req_id=req_id, prompt_len=prompt_len,
        remaining_prefill=(
            prompt_len if remaining_prefill is None else remaining_prefill
        ),
        remaining_decode=remaining_decode, submit_step=submit_step,
        admit_step=admit_step, slot=slot, pages_needed=pages_needed,
        preempt_count=preempt_count, preempt_step=preempt_step,
        tenant=tenant, priority=priority,
    )


# ------------------------------------------------------- policy layer --

class TestTenantPolicy:
    def test_registry_and_validation(self):
        assert isinstance(get_scheduler("tenant"), TenantQuotaPolicy)
        pol = TenantQuotaPolicy({"a": {"max_pages": 4}})
        assert pol.quotas["a"] == TenantQuota(max_pages=4)
        with pytest.raises(ValueError):
            TenantQuota(max_pages=0)
        with pytest.raises(ValueError):
            TenantQuota(max_step_tokens=-1)
        with pytest.raises(ValueError):
            TenantQuotaPolicy(patience=0)
        assert not TenantQuotaPolicy().hol_blocking

    def test_admission_latency_class_first(self):
        """Within the fresh window: latency class ahead of throughput,
        FIFO (wait_anchor, then req_id) within each class."""
        pol = TenantQuotaPolicy(patience=100)
        ws = [
            _v(1, priority="throughput", submit_step=0),
            _v(2, priority="latency", submit_step=5),
            _v(3, priority="throughput", submit_step=1),
            _v(4, priority="latency", submit_step=2),
        ]
        order = [v.req_id for v in pol.admission_order(ws, now=10)]
        assert order == [4, 2, 1, 3]

    def test_aging_guard_beats_class_rank(self):
        """Starvation freedom: a throughput request past the patience
        window is promoted to strict FIFO ahead of EVERY fresh latency
        request - a latency burst delays bulk work, never starves it."""
        pol = TenantQuotaPolicy(patience=16)
        ws = [
            _v(1, priority="throughput", submit_step=0),   # starved
            _v(2, priority="latency", submit_step=30),
            _v(3, priority="throughput", submit_step=10),  # starved
            _v(4, priority="latency", submit_step=31),
        ]
        order = [v.req_id for v in pol.admission_order(ws, now=32)]
        assert order == [1, 3, 2, 4]

    def test_aging_anchors_on_preempt_step(self):
        """The wait clock restarts at page-out (the shared wait_anchor
        rule): a just-preempted request is FRESH, not starved."""
        pol = TenantQuotaPolicy(patience=16)
        ws = [
            _v(1, priority="throughput", submit_step=0,
               preempt_count=1, preempt_step=30),
            _v(2, priority="latency", submit_step=29),
        ]
        order = [v.req_id for v in pol.admission_order(ws, now=32)]
        assert order == [2, 1]

    def test_plan_admission_withholds_over_quota(self):
        """The quota gate charges admitted candidates sequentially: with
        tenant 'a' capped at 8 pages and 3 running pages already, a
        4-page candidate fits (7 <= 8) but the NEXT 4-page one would
        overshoot (11 > 8) and is withheld; an unquota'd tenant and a
        quota'd-but-under one pass through untouched."""
        pol = TenantQuotaPolicy({"a": TenantQuota(max_pages=8)})
        running = [_v(9, tenant="a", slot=0, admit_step=0, pages_needed=3)]
        waiting = [
            _v(1, tenant="a", submit_step=0, pages_needed=4),
            _v(2, tenant="a", submit_step=1, pages_needed=4),
            _v(3, tenant="b", submit_step=2, pages_needed=40),
        ]
        plan = [v.req_id for v in pol.plan_admission(waiting, running)]
        assert plan == [1, 3]
        # quota freed (tenant 'a' idle): both fit again, 4 + 4 <= 8
        plan = [v.req_id for v in pol.plan_admission(waiting, [])]
        assert plan == [1, 2, 3]

    def test_base_plan_admission_ignores_running(self):
        """The base hook is a pure delegation to admission_order - the
        pre-existing policies are unaffected by the new surface."""
        ws = [_v(1), _v(2)]
        pol = SchedulerPolicy()
        assert [v.req_id for v in pol.plan_admission(ws, [_v(9, slot=0)])] \
            == [v.req_id for v in pol.admission_order(ws)]

    def test_plan_prefill_per_tenant_token_cap(self):
        """max_step_tokens caps each tenant's grants per step: the
        flooding tenant's second row gets only its quota remainder
        (page-aligned down), and the budget freed flows to the other
        tenant instead of being discarded."""
        pol = TenantQuotaPolicy(
            {"flood": TenantQuota(max_step_tokens=24)}
        )
        vs = [
            _v(1, tenant="flood", remaining_prefill=16, pages_needed=2),
            _v(2, tenant="flood", remaining_prefill=40, pages_needed=5),
            _v(3, tenant="quiet", remaining_prefill=40, pages_needed=5),
        ]
        plan = pol.plan_prefill(
            vs, n_decode=0, budget=64, chunk=16, page_size=8, max_rows=4,
        )
        # (1,16) spends 16 of flood's 24; row 2 gets 8 (aligned down from
        # its 16-token chunk); quiet takes a full chunk from the budget
        assert plan == [(1, 16), (2, 8), (3, 16)]

    def test_plan_prefill_latency_class_first(self):
        pol = TenantQuotaPolicy()
        vs = [
            _v(1, priority="throughput", remaining_prefill=8),
            _v(2, priority="latency", remaining_prefill=40),
        ]
        plan = pol.plan_prefill(
            vs, n_decode=0, budget=16, chunk=16, page_size=8, max_rows=4,
        )
        assert plan == [(2, 16)]      # latency head takes the budget

    def test_choose_victim_class_aware(self):
        """Victim: never-preempted first (anti-thrash), then throughput
        class over latency, then largest footprint."""
        pol = TenantQuotaPolicy()
        running = [
            _v(1, priority="latency", slot=0, admit_step=0, pages_needed=9),
            _v(2, priority="throughput", slot=1, admit_step=1,
               pages_needed=3),
            _v(3, priority="throughput", slot=2, admit_step=2,
               pages_needed=5),
        ]
        assert pol.choose_victim(running, now=5).req_id == 3
        # the only throughput candidates already paid once -> latency pays
        paid = [
            _v(1, priority="latency", slot=0, admit_step=0, pages_needed=9),
            _v(2, priority="throughput", slot=1, admit_step=1,
               pages_needed=3, preempt_count=1, preempt_step=3),
        ]
        assert pol.choose_victim(paid, now=5).req_id == 1
        assert pol.choose_victim([], now=5) is None


# ------------------------------------------------- engine bit-identity --

PROMPT_LENS = (37, 21, 45, 12)
TENANTS = ("bulk", "interactive", "bulk", "interactive")
PRIOS = ("throughput", "latency", "throughput", "latency")
GEN = 4


@pytest.fixture(scope="module")
def tiny_bundle():
    from repro.configs import get_config
    from repro.models.model_zoo import build

    cfg = get_config("qwen3-4b").reduced()
    bundle = build(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    return bundle, params


@pytest.fixture(scope="module")
def workload(tiny_bundle):
    rng = np.random.default_rng(0)
    vocab = tiny_bundle[0].cfg.vocab_size
    return [list(rng.integers(0, vocab, n)) for n in PROMPT_LENS]


def _tenant_policy():
    # bulk capped at 7 pages: its two requests (6 and 7 pages at
    # page_size 8) can never run simultaneously - the quota gate
    # actually fires during the serve - plus a per-step token throttle.
    return TenantQuotaPolicy(
        {"bulk": TenantQuota(max_pages=7, max_step_tokens=16)},
        patience=64,
    )


def _serve(bundle, params, prompts, *, tenants=None, priorities=None, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("num_pages", 40)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("prefill_chunk", 16)
    eng = ServeEngine(bundle, params, **kw)
    reqs = [
        eng.submit(
            p, GEN,
            tenant=(tenants[i] if tenants else DEFAULT_TENANT),
            priority=(priorities[i] if priorities else "throughput"),
        )
        for i, p in enumerate(prompts)
    ]
    eng.run_to_completion()
    return [r.generated for r in reqs], eng


@pytest.mark.parametrize("pipeline_depth", [0, 1], ids=["sync", "async"])
@pytest.mark.parametrize("dtype", ["bf16", "fp8_e4m3", "int8"])
def test_tenant_scheduling_bit_identity_matrix(tiny_bundle, workload,
                                               dtype, pipeline_depth):
    """THE PR contract: for a fixed routing outcome (one engine), tenant
    quotas + priority classes reorder WHEN work runs but never change a
    request's tokens - streams bit-identical to the tenant-blind FCFS
    serve, sync AND async, at raw and quantized pool dtypes."""
    ref, _ = _serve(*tiny_bundle, workload, scheduler="fcfs",
                    cache_dtype=dtype)
    got, eng = _serve(
        *tiny_bundle, workload, scheduler=_tenant_policy(),
        cache_dtype=dtype, pipeline_depth=pipeline_depth,
        tenants=TENANTS, priorities=PRIOS,
    )
    assert got == ref
    assert eng.stats()["inflight"] == 0


def test_quota_withheld_never_preempts(tiny_bundle, workload):
    """Withheld != page-starved: tenant 'bulk' at its page cap keeps its
    second request WAITING even with preemption armed at patience 1 and
    a pool full of free pages - quota blocking must not page anyone out.
    The withheld request admits when the first finishes, bit-exactly."""
    bundle, params = tiny_bundle
    eng = ServeEngine(
        bundle, params, max_batch=4, num_pages=40, page_size=8,
        max_seq_len=64, prefill_chunk=16,
        scheduler=TenantQuotaPolicy({"bulk": TenantQuota(max_pages=7)}),
        preemption=True, preempt_patience=1,
    )
    ra = eng.submit(workload[2], GEN, tenant="bulk")   # 45 + 4 -> 7 pages
    rb = eng.submit(workload[0], GEN, tenant="bulk")   # 37 + 4 -> 6 pages
    for _ in range(4):
        eng.step()
    assert ra.state == "running" and rb.state == "waiting"
    assert eng.allocator.free_pages > rb.pages_needed(8)  # pool NOT short
    eng.run_to_completion()
    assert eng.preemptions == 0
    for r, w in ((ra, 2), (rb, 0)):
        assert r.generated == chunked_cold_reference(
            bundle, params, workload[w], GEN, page_size=8, prefill_chunk=16,
        )


def test_preempt_resume_under_tenant_policy(tiny_bundle, workload):
    """Genuine page starvation still preempts under the tenant policy,
    and the class-aware victim rule picks the throughput straggler for
    the latency arrival; the resumed stream reproduces the uninterrupted
    serve bitwise (the chunk-exact convention survives the new policy)."""
    bundle, params = tiny_bundle
    eng = ServeEngine(
        bundle, params, max_batch=2, num_pages=12, page_size=8,
        max_seq_len=64, prefill_chunk=16, prefix_cache=True,
        preemption=True, preempt_patience=2, scheduler=TenantQuotaPolicy(),
    )
    ra = eng.submit(workload[2], 12, tenant="bulk", priority="throughput")
    for _ in range(3):
        eng.step()
    assert ra.generated, "straggler should be mid-decode before preemption"
    rb = eng.submit(workload[0], GEN, tenant="interactive",
                    priority="latency")
    eng.run_to_completion()
    assert eng.preemptions >= 1 and ra.preempt_count >= 1
    assert rb.preempt_count == 0          # latency class kept its pages
    for r, prompt, gen in ((ra, workload[2], 12), (rb, workload[0], GEN)):
        assert r.generated == chunked_cold_reference(
            bundle, params, prompt, gen, page_size=8, prefill_chunk=16,
        )


def test_cancel_releases_quota(tiny_bundle, workload):
    """Cancel under quota pressure: cancelling the running request frees
    its tenant's quota, the withheld sibling admits on the next step and
    serves bit-exactly - no preemption, no stuck accounting."""
    bundle, params = tiny_bundle
    eng = ServeEngine(
        bundle, params, max_batch=4, num_pages=40, page_size=8,
        max_seq_len=64, prefill_chunk=16,
        scheduler=TenantQuotaPolicy({"bulk": TenantQuota(max_pages=7)}),
        preemption=True, preempt_patience=1,
    )
    ra = eng.submit(workload[2], 12, tenant="bulk")
    rb = eng.submit(workload[0], GEN, tenant="bulk")
    for _ in range(4):
        eng.step()
    assert ra.state == "running" and rb.state == "waiting"
    assert eng.cancel(ra.req_id)
    eng.run_to_completion()
    assert eng.preemptions == 0
    assert ra.state == "cancelled" and rb.state == "finished"
    assert rb.generated == chunked_cold_reference(
        bundle, params, workload[0], GEN, page_size=8, prefill_chunk=16,
    )


def test_submit_validation(tiny_bundle):
    bundle, params = tiny_bundle
    eng = ServeEngine(
        bundle, params, max_batch=1, num_pages=8, page_size=8,
        max_seq_len=32,
    )
    with pytest.raises(ValueError):
        eng.submit([1, 2, 3], 2, tenant="")
    with pytest.raises(ValueError):
        eng.submit([1, 2, 3], 2, priority="urgent")
    assert "latency" in PRIORITY_CLASSES


# --------------------------------------------------- routing decisions --

class _FakeEngine:
    """The slice of the ServeEngine surface routing reads: queue depth,
    slot occupancy, and (for affinity) the prefix trie probe."""

    def __init__(self, waiting=0, running=0, cache=None):
        self.waiting = [None] * waiting
        self.num_running = running
        self.prefix_cache = cache


def _group(engines, routing):
    grp = EngineReplicaGroup.__new__(EngineReplicaGroup)
    grp.engines = list(engines)
    grp.routing = routing
    grp._rr = 0
    grp._req_counter = 0
    grp._owner = {}
    return grp


class TestReplicaRouting:
    def test_equal_loads_degenerate_to_round_robin(self):
        """The pinned contract of the pre-existing schedules: an upfront
        burst onto idle replicas deals i::n exactly (the rotating-cursor
        tiebreak), for both the least-loaded and affinity modes."""
        for routing in ("least", "affinity"):
            engines = [_FakeEngine() for _ in range(3)]
            grp = _group(engines, routing)
            picks = []
            for _ in range(6):
                eng = grp._route([1, 2, 3])
                eng.num_running += 1        # submit occupies the replica
                picks.append(engines.index(eng))
            assert picks == [0, 1, 2, 0, 1, 2], routing

    def test_least_loaded_fills_post_cancel_gap(self):
        """Regression (this PR): strict rotation kept dealing i::n after
        a cancel emptied one replica, leaving it idle while its peers
        queued.  Least-loaded routes the next submissions into the gap;
        the legacy "rr" mode preserves the blind deal for schedule
        reproduction."""
        engines = [_FakeEngine(waiting=2, running=1),
                   _FakeEngine(waiting=0, running=0),   # drained by cancel
                   _FakeEngine(waiting=2, running=1)]
        grp = _group(engines, "least")
        grp._rr = 0                          # cursor parked at replica 0
        assert grp._route([5]) is engines[1]
        blind = _group(engines, "rr")
        assert blind._route([5]) is engines[0]   # the pre-fix behavior

    def test_affinity_prefers_longest_cached_prefix(self):
        """The replica holding the longest cached prefix wins even when
        it is busier; ties on probe length fall back to least-loaded
        among the tied; no hit anywhere falls back to least-loaded."""
        cache = _trie(4, 3)                            # 3 pages cached
        short = _trie(4, 1)                            # 1 page cached
        engines = [
            _FakeEngine(waiting=0, running=0, cache=short),
            _FakeEngine(waiting=3, running=2, cache=cache),  # busy but warm
            _FakeEngine(waiting=0, running=0, cache=None),
        ]
        grp = _group(engines, "affinity")
        assert grp._route(list(range(16))) is engines[1]
        # no cached prefix for THIS prompt -> least-loaded fallback
        assert grp._route([99, 98, 97, 96]) in (engines[0], engines[2])

    def test_probe_len_is_a_pure_read(self):
        """Routing probes must not perturb cache state: no refcounts, no
        clock bumps, no hit/miss accounting (a probe is not a match)."""
        cache = _trie(4, 2)
        before = (cache.hits, cache.misses, cache.cached_pages,
                  cache.evictable_pages)
        assert cache.probe_len(list(range(8))) == 8
        assert cache.probe_len(list(range(4))) == 4
        assert cache.probe_len([42] * 8) == 0
        assert (cache.hits, cache.misses, cache.cached_pages,
                cache.evictable_pages) == before

    def test_routing_validation(self):
        assert set(ROUTING_MODES) == {"affinity", "least", "rr"}
