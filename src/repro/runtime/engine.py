"""Continuous-batching serving engine over the paged KV cache.

The engine owns the *host-side* mechanism (request queue, slots, page
accounting, prefix-cache references, per-request cursors, preemption
plumbing) around at most two *device-side* jitted calls per step - one
BATCHED chunked-prefill call and one fully-batched decode call - both
shape-static, so there are exactly two compilations for the whole serving
session.  Every scheduling *decision* - admission order, which requests'
prefill chunks ride this step's batch and at what size, who gets preempted
- is delegated to a pluggable :class:`~repro.runtime.scheduler
.SchedulerPolicy` (``scheduler=`` "fcfs" | "sjf" | "mixed").

Request lifecycle::

    submit() -> WAITING --admission--> RUNNING(prefill) -> RUNNING(decode)
                 ^  |          (slot + pages granted,            |
                 |  |           shared prefix pages referenced)  v
                 |  +<---- insufficient slot/pages     FINISHED (owned pages
                 |                                     freed or donated to the
                 +--- preempt-to-page-out              prefix cache, slot
                      (pages donated/freed,            reusable next step)
                       request re-queued)

  * **Admission** happens at the top of every :meth:`step` in the policy's
    order - continuous batching, no draining barrier.  Admission stays
    *conservative* (worst-case page need must be coverable) but charges
    only **non-shared** pages when the prefix cache is enabled; refcount-0
    cache pages are evicted on demand.  FCFS/mixed keep intentional
    head-of-line blocking; SJF skips blocked candidates (with an aging
    guard against starvation).
  * **Batched chunked prefill** (default): each step runs prompt chunks of
    up to ``prefill_batch`` still-prefilling requests through ONE call of
    the chunk-exact paged prefill (kernels/pasa_paged_prefill.py) - each
    row carries its own position offset, valid length, and page-table row;
    ragged tails are right-padded to the static ``(prefill_batch,
    prefill_chunk)`` grid and write to the null page.  The policy splits a
    per-step token budget (``step_token_budget``; decode rows charge one
    token each) across the rows - Sarathi-style mixing generalized from
    the PR-2 one-chunk-per-step loop, which ``prefill_batch=1`` still
    reproduces exactly.
  * **Preemption** (``preemption=True``): when the policy's head admission
    candidate has been page-starved for ``preempt_patience`` consecutive
    steps, the policy picks a running victim to page out: its
    prefill-written full prompt pages are DONATED to the prefix cache
    (their bytes are a pure function of the token prefix - the chunk-exact
    property), everything else is freed, and the request re-queues with
    its generated-so-far tokens recorded for replay.  Resume is a prefix
    -cache hit + re-prefill of only the private prompt tail + teacher
    -forced decode replay of the recorded tokens - each decode step is the
    same pure function of (pool bytes, fed token) as in the uninterrupted
    serve, so the resumed stream is BIT-IDENTICAL to never having been
    preempted (tests/test_scheduler.py, bf16 and int8 pools).
  * **Sampling**: ``temperature > 0`` switches the on-device token choice
    from argmax to temperature + top-k categorical sampling, keyed per
    (request id, token index) - so sampled streams are reproducible and,
    like greedy ones, bit-invariant to scheduling, batching, preemption,
    and policy swaps.  ``temperature=0`` (default) keeps the exact greedy
    path.
  * **Pages** are granted at admission; freed pages recycle WITHOUT
    scrubbing (masked valid-column shift; see runtime/paged_cache.py).  On
    finish - as on preemption - full prompt pages are donated to the
    prefix cache when it is enabled.  With ``trim_high``/``trim_low``
    watermarks set, the engine also trims refcount-0 cache pages in the
    background: when pool occupancy exceeds ``trim_high`` it evicts down
    toward ``trim_low`` at the top of the step, so admission normally
    finds free pages instead of paying eviction latency inline (the O(1)
    ``evictable_pages`` counter makes the per-step probe free).
  * **Inactive slots** still execute in the decode call (shape-static
    batching); their page-table rows are nulled in the decode view and
    their writes land in null page 0 (runtime/paged_cache.py).

PASA / page-size interaction: the engine defaults ``page_size`` to the
model's PASA block length (``cfg.attention.block_kv``), making one page ==
one PASA shift block; both paged kernels compute their per-block key shift
page-locally, so page granularity and shift granularity coincide - the
property that makes raw-K/V page sharing exact (runtime/prefix_cache.py).

Async pipelining (``pipeline_depth >= 1``): every step is split into a
host-side PLAN phase (trim, admission, policy decisions, page-table
assembly - pure host, no device sync) and a device DISPATCH phase (the
two jitted calls, enqueued asynchronously).  The host never reads a
sampled token back on the per-step path: the next-token feed lives ON
DEVICE (``_next_dev``, composed with host-known overrides - teacher
forcing, replay - by a tiny eager select at dispatch), finish decisions
are COUNT-based (every decode row emits exactly one token, so
``len(generated)`` advances deterministically at dispatch), and emitted
values are materialized lag-``pipeline_depth`` by :meth:`_retire_one` -
AFTER the next step has been dispatched, so the readback overlaps device
execution.  The only legal synchronous readbacks are the annotated drain
points (``@_drain_point``; enforced by tests/test_async_guard.py):
retirement itself, and :meth:`drain` - called before a plan decision that
genuinely depends on token VALUES (preemption must record the victim's
generated tokens for replay; :meth:`cancel` mid-flight).  Because both
modes run the SAME compiled programs on bit-identical inputs (page
tables and token vectors are freshly copied per dispatch - double
-buffered - and the pool is donated through the call chain, which also
device-orders page reuse and prefix-cache donation across overlapping
steps), the async engine's token streams and final page bytes are
BIT-IDENTICAL to the synchronous engine's (tests/test_async_engine.py).
"""

from __future__ import annotations

import dataclasses
import math
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime.paged_cache import (
    NULL_PAGE,
    PageAllocator,
    capture_pages,
    paged_bytes,
    paged_bytes_per_device,
    pool_dtype_name,
    pool_shardings,
    resolve_pool_dtype,
    restore_pages,
    touched_pages,
)
from repro.runtime.prefix_cache import RadixPrefixCache
from repro.runtime.scheduler import (
    DEFAULT_TENANT,
    PRIORITY_CLASSES,
    RequestView,
    get_scheduler,
)
from repro.runtime.spec_decode import get_drafter
from repro.runtime.telemetry import Telemetry, _drain_point

WAITING = "waiting"
RUNNING = "running"
FINISHED = "finished"
CANCELLED = "cancelled"

#: Version of the ``stats()`` dict schema shared by :class:`ServeEngine`
#: and :class:`EngineReplicaGroup` (documented in runtime/README.md
#: "Observability").  Both expose the SAME shared keys; the group view is
#: a true aggregation of its replicas plus ``replicas`` / ``engines``.
#: Bump on any key add/remove/retype; tests/test_telemetry.py pins the
#: key set against this version.  v2: added ``speculate`` (config) and
#: the ``spec`` tally sub-dict (speculative-decoding counters).
STATS_SCHEMA = 2

#: How the replica group aggregates each shared stats() key: additive
#: tallies and capacity totals SUM; clocks and per-device peaks take the
#: MAX; uniform engine configuration passes through from replica 0.
_STATS_SUM = (
    "running", "waiting", "finished", "free_pages", "live_pages",
    "cache_bytes", "preemptions", "trimmed_pages", "last_step_tokens",
    "inflight", "cancellations",
)
_STATS_MAX = ("steps", "cache_bytes_per_device", "max_step_tokens")
_STATS_CONFIG = (
    "page_size", "pool_dtype", "chunked_prefill", "scheduler",
    "prefill_batch", "step_token_budget", "temperature", "pipeline_depth",
    "speculate",
)


#: One fused jitted select for the async hot path (feed composition and
#: the device-resident ``_next_dev`` update).  An exact int32 lane pick -
#: jit-vs-eager changes dispatch cost, never a bit - but collapsing the
#: eager transfer+where chains into a single dispatch matters on the
#: per-step path: async mode pays this INSTEAD of a readback, so its
#: overhead bounds how much overlap can show up as wall-clock.
_select_i32 = jax.jit(lambda known, host, dev: jnp.where(known, host, dev))


# ``_drain_point`` - the marker for LEGAL synchronous-readback sites of
# the async pipeline - now lives in runtime/telemetry.py (telemetry's
# numerics probe shares the discipline and the module must not import the
# engine); it is re-exported here because tests/test_async_guard.py
# parses BOTH modules for the decorator by name.


def dense_greedy_reference(bundle, params, prompt, max_new_tokens: int):
    """Token-by-token greedy decode on a fresh DENSE (B=1) cache.

    The bit-equivalence oracle for the TOKEN-BY-TOKEN engine mode
    (``chunked_prefill=False``; examples/serve_paged.py, tests/test_paged.py):
    it exercises only ``bundle.serve_step`` + the dense cache, none of the
    paged machinery, and must produce token-for-token the same greedy
    continuation as a request served through :class:`ServeEngine` in that
    mode.  Chunked prefill uses the chunk-exact convention instead (same
    exact softmax, different fp16 rounding on interior rows); its oracle is
    :func:`chunked_cold_reference`.
    """
    step = jax.jit(lambda p, t, pos, c: bundle.serve_step(p, t, pos, c))
    cache = bundle.init_cache(1, len(prompt) + max_new_tokens)
    tok = jnp.asarray([prompt[0]], jnp.int32)
    out = []
    for i in range(len(prompt) + max_new_tokens - 1):
        logits, cache = step(params, tok, jnp.full((1,), i, jnp.int32), cache)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if i + 1 < len(prompt):
            tok = jnp.asarray([prompt[i + 1]], jnp.int32)
        else:
            tok = nxt
            out.append(int(nxt[0]))
    return out


def chunked_cold_reference(
    bundle, params, prompt, max_new_tokens: int, *,
    page_size: int = 16, prefill_chunk: Optional[int] = None,
    cache_dtype=jnp.bfloat16, **engine_kwargs,
):
    """Cold (empty-prefix-cache) chunked-prefill serve of one request.

    The hit-vs-cold oracle: a prefix-cache-hit serve of the same request
    must match this token-for-token AND page-for-page bit-identically,
    REGARDLESS of the chunk size used by either side (the chunk-exact
    convention is schedule-invariant).  Extra ``engine_kwargs`` (scheduler,
    sampling, budget, ...) pass through to the engine - every one of them
    is output-bit-preserving for a single request."""
    total = len(prompt) + max_new_tokens
    eng = ServeEngine(
        bundle, params, max_batch=1,
        num_pages=1 + math.ceil(max(total - 1, 1) / page_size),
        page_size=page_size, max_seq_len=total,
        prefill_chunk=prefill_chunk, cache_dtype=cache_dtype,
        **engine_kwargs,
    )
    r = eng.submit(prompt, max_new_tokens)
    eng.run_to_completion()
    return r.generated


@dataclasses.dataclass
class Request:
    """One generation request and its lifecycle bookkeeping."""

    req_id: int
    prompt: List[int]
    max_new_tokens: int
    state: str = WAITING
    # multi-tenant attribution: quota accounting, priority-class ordering,
    # and per-tenant telemetry.  Purely host-side scheduling inputs - they
    # never reach the device, so tenant labels cannot change output bits.
    tenant: str = DEFAULT_TENANT
    priority: str = "throughput"
    generated: List[int] = dataclasses.field(default_factory=list)
    # engine-step timestamps (continuous-batching latency accounting)
    submit_step: int = -1
    admit_step: int = -1
    first_token_step: int = -1
    finish_step: int = -1
    # placement while RUNNING
    slot: int = -1
    pages: List[int] = dataclasses.field(default_factory=list)  # owned only
    cursor: int = 0      # next cache position to be written (decode phase)
    # chunked-prefill bookkeeping
    prefill_pos: int = 0     # next prompt position whose K/V is not written
    cached_len: int = 0      # prompt tokens served from the prefix cache
    prefix_nodes: list = dataclasses.field(default_factory=list)
    # preemption bookkeeping
    replay: List[int] = dataclasses.field(default_factory=list)
    blocked_steps: int = 0   # consecutive page-starved admission attempts
    preempt_count: int = 0
    preempt_step: int = -1
    # async pipelining: entries of ``generated`` whose VALUE is still on
    # device (None placeholders, filled in dispatch order at retirement).
    # The COUNT len(generated) is always exact - it advances at dispatch -
    # so finish/budget/policy decisions never wait on a readback.
    pending: int = 0
    # speculative decoding: True between dispatching a K-draft verify for
    # this request and retiring it.  The accepted COUNT is the one
    # speculation value the host cannot know at dispatch, so a verifying
    # request sits out subsequent plans (its cursor and ``generated`` are
    # frozen) until :meth:`ServeEngine._retire_one` materializes it.
    verifying: bool = False

    @property
    def total_len(self) -> int:
        return len(self.prompt) + self.max_new_tokens

    def pages_needed(self, page_size: int) -> int:
        # The request writes cache positions 0..total_len-2 (the final
        # generated token is returned, never fed back) - so only
        # total_len - 1 positions need page backing.
        return math.ceil(max(self.total_len - 1, 1) / page_size)


@dataclasses.dataclass
class _InflightStep:
    """Device work dispatched for one engine step whose sampled tokens
    have not been read back yet.  ``*_tok`` hold the (possibly still
    executing) device outputs; ``*_emits`` record which
    ``(request, generated-index, output-row)`` each value belongs to -
    fixed at dispatch, so retirement is a pure fill-in."""

    step_no: int
    prefill_tok: Optional[jax.Array] = None
    prefill_emits: List[Tuple[Request, int, int]] = dataclasses.field(
        default_factory=list
    )
    decode_tok: Optional[jax.Array] = None
    decode_emits: List[Tuple[Request, int, int]] = dataclasses.field(
        default_factory=list
    )
    # speculative verify bookkeeping: when set, ``decode_tok`` is the
    # (B, K+1) per-position verifier output and ``verify_m`` the device
    # (B,) accepted-count vector - the ONE new host-visible speculation
    # value, read at retirement exactly like tokens.  ``spec_rows``
    # records (request, slot, drafts proposed) fixed at dispatch.
    verify_m: Optional[jax.Array] = None
    spec_rows: List[Tuple[Request, int, int]] = dataclasses.field(
        default_factory=list
    )


def _make_sampler(temperature: float, top_k: int, base_key):
    """(logits (B, V), req_ids (B,), token_idx (B,)) -> tokens (B,) int32.

    The per-row key is ``fold_in(fold_in(base_key, req_id), token_idx)``,
    derived INSIDE the jitted step from two int32 rows - no per-row eager
    dispatches on the per-token host path."""
    temp = float(temperature)

    def keyed(rid, idx):
        return jax.random.fold_in(jax.random.fold_in(base_key, rid), idx)

    def sample(logits, req_ids, token_idx):
        lg = logits.astype(jnp.float32) / jnp.asarray(temp, jnp.float32)
        if top_k > 0:
            kth = jax.lax.top_k(lg, top_k)[0][..., -1:]
            lg = jnp.where(lg < kth, -jnp.inf, lg)
        keys = jax.vmap(keyed)(req_ids, token_idx)
        return jax.vmap(jax.random.categorical)(keys, lg).astype(jnp.int32)

    return sample


class ServeEngine:
    """Paged-KV continuous-batching engine over a ModelBundle.

    Args:
      bundle: model bundle; must expose the paged interface
        (``bundle.supports_paged`` - transformer families).
      params: model parameters.
      max_batch: number of device batch slots (B of the jitted decode step).
      num_pages: physical pages in the pool, *including* the reserved null
        page 0 (so ``num_pages - 1`` are allocatable).
      page_size: tokens per page; defaults to the model's PASA block
        length so page == shift-block granularity (see module doc).
      max_seq_len: longest sequence (prompt + generation) any single
        request may reach.  Sets the page-table width - which is also the
        length of the KV view each decode step attends over - AND the
        submit-time admissibility bound: requests with
        ``len(prompt) + max_new_tokens > max_seq_len`` are rejected at
        :meth:`submit` (they could never be served under the bounded page
        table, and would otherwise wedge the queue forever).
        Default: the page table's physical capacity,
        ``(num_pages - 1) * page_size``.
      chunked_prefill: prefill prompts in chunks through the paged prefill
        path (default) instead of token-by-token through the decode step.
      prefill_chunk: per-row chunk width of the batched prefill call; must
        be a multiple of ``page_size`` (page-aligned chunk boundaries are
        what make chunked prefill bit-exact).  Default: ``8 * page_size``.
      prefix_cache: share identical prompt-prefix K/V pages across requests
        via a radix prefix cache (requires ``chunked_prefill``).
      cache_dtype: pool storage dtype - a jnp dtype, or one of the
        ``runtime.paged_cache.POOL_DTYPES`` names ("bf16", "fp8_e4m3",
        "int8").  Quantized dtypes store shift-centered 8-bit codes plus
        per-page scale/shift sidecars carried with the page through every
        lifecycle operation.
      scheduler: a policy name ("fcfs" | "sjf" | "mixed") or a
        :class:`~repro.runtime.scheduler.SchedulerPolicy` instance.  Every
        policy produces bit-identical per-request outputs (scheduling is
        latency-only); "fcfs" with ``prefill_batch=1`` reproduces the
        pre-policy engine schedule exactly.
      prefill_batch: rows of the batched prefill call (static shape; one
        compilation).  Default: ``max_batch``.  1 = the sequential
        one-request-per-step baseline (benchmarks/scheduler_burst.py).
      step_token_budget: global per-step token budget the policy divides
        between decode rows (1 token each, charged first) and prefill
        chunk tokens.  None (default) = unlimited.  Must be at least
        ``page_size`` so prefill can always eventually progress.
      preemption: enable preempt-to-page-out (see module doc).
      preempt_patience: consecutive page-starved steps the head admission
        candidate tolerates before the policy may pick a victim.
      trim_high / trim_low: background prefix-cache trimming watermarks as
        fractions of the allocatable pool (both or neither; requires
        ``prefix_cache``).  When live pages exceed ``trim_high`` of the
        pool, refcount-0 cache pages are evicted down toward ``trim_low``
        at the top of the step.
      pipeline_depth: device steps allowed in flight AHEAD of token
        readback.  0 (default) = synchronous: every step's tokens are
        materialized before :meth:`step` returns, exactly the pre-async
        engine.  1 = async pipelining: step N+1 is planned and dispatched
        from optimistically-advanced host state while step N's tokens are
        still on device; N's values are filled in afterwards by
        :meth:`_retire_one`, overlapping host work with device execution.
        Both modes run the SAME compiled programs on bit-identical inputs,
        so streams and page bytes are mode-invariant (module doc).
      on_token: optional ``callback(request, token_index, token)`` invoked
        as each generated token is MATERIALIZED (at retirement, in
        dispatch order) - the streaming-emission hook.  In async mode the
        callback for step N fires after step N+1 was dispatched; use
        :meth:`drain` to force all pending emissions at a stream boundary.
      temperature / top_k / sample_seed: serve-path sampling.
        ``temperature=0`` (default) = greedy argmax, bit-identical to the
        pre-sampling engine.  ``temperature>0`` samples from the
        temperature-scaled, optionally top-k-truncated distribution with a
        per-(request, token index) PRNG key derived from ``sample_seed`` -
        deterministic, and independent of scheduling.
      speculate: draft tokens per decode row per step (K).  0 (default)
        = plain one-token-per-row decode.  K >= 1 enables
        SELF-SPECULATIVE decoding: a host-side proposer (``draft``)
        guesses up to K continuation tokens per decode row from the
        request's own prompt+generated history, and the decode dispatch
        widens into ONE jitted verify call that runs feed + drafts
        through K+1 chained decode sub-steps, computes the accepted
        count m = 1 + longest draft prefix matching the model's own
        choice ON DEVICE, and restores the KV bytes of every rejected
        position (the accepted count is the one new host-visible value,
        read at retirement like tokens - pipeline modes unchanged).
        Accepted tokens therefore ALWAYS equal the non-speculative
        trajectory: greedy streams and non-null page bytes are
        bit-identical speculation-on vs -off, and sampled streams keep
        the per-(request, token index) keying (tests/test_spec_decode
        .py).  Requires ``chunked_prefill``; draft tokens charge the
        ``step_token_budget`` via the policy's ``plan_speculation``
        hook.  See runtime/README.md "Speculative decoding".
      draft: the draft proposer when ``speculate > 0`` - a name from
        ``runtime.spec_decode.DRAFTERS`` ("ngram"), a DraftProposer
        subclass, or an instance.  Proposal quality affects ONLY
        latency (steps per token), never output bits.
      mesh: optional ``jax.sharding.Mesh`` with a ``model`` axis.  The
        page pool's leaves are laid out kv-head-split over that axis
        (runtime/paged_cache.pool_shardings) and BOTH jitted device calls
        run under a fully-MANUAL shard_map with explicit jit-boundary
        NamedShardings - tokens, positions, and page tables replicated,
        the pool at its kv-head sharding on input AND output (pool
        donation preserved), params replicated.  Inside the manual
        region no SPMD partitioner runs, and the pool boundary
        (:meth:`_make_pool_io`) is the ONLY distributed code: sharded
        leaves are all-gathered to full width on entry and the updated
        pool is sliced back to this device's shard on exit, so the
        interior is the UNMODIFIED 1-device step computation and the
        sharded serve's token streams AND page bytes are BIT-IDENTICAL
        to the single-device serve at every pool dtype, with per-device
        pool RESIDENCY ~= 1/model-axis-size
        (tests/test_sharded_serving.py).  When ``n_kv_heads`` does not
        divide the model-axis size the pool falls back to replication
        (see runtime/README.md for the ring-PASA compute fallback at the
        kernel entry points).  Host-side state (allocator, page tables,
        prefix cache, scheduling) is sharding-oblivious.  Data-parallel
        replicas over a 2-D mesh are built by
        :class:`EngineReplicaGroup`.
      telemetry: optional :class:`~repro.runtime.telemetry.Telemetry` -
        structured step tracing, the metrics registry (threaded through
        the allocator and prefix cache too), and the sampled numerics
        probe.  BIT-NEUTRAL: every hook reads host state the engine
        already maintains and nothing it records feeds back into a device
        call or scheduling decision, so a telemetry-on serve is
        bit-identical (streams and page bytes) to a telemetry-off serve
        in every mode (tests/test_telemetry.py).  Device-derived
        readings are collected only at retirement drain points (the
        probe's own ``@_drain_point`` read), preserving the async
        pipeline's no-readback discipline.
    """

    def __init__(
        self,
        bundle,
        params,
        *,
        max_batch: int = 4,
        num_pages: int = 64,
        page_size: Optional[int] = None,
        max_seq_len: Optional[int] = None,
        chunked_prefill: bool = True,
        prefill_chunk: Optional[int] = None,
        prefix_cache: bool = False,
        cache_dtype=jnp.bfloat16,
        scheduler="fcfs",
        prefill_batch: Optional[int] = None,
        step_token_budget: Optional[int] = None,
        preemption: bool = False,
        preempt_patience: int = 4,
        trim_high: Optional[float] = None,
        trim_low: Optional[float] = None,
        temperature: float = 0.0,
        top_k: int = 0,
        sample_seed: int = 0,
        speculate: int = 0,
        draft="ngram",
        mesh=None,
        pipeline_depth: int = 0,
        on_token: Optional[Callable[[Request, int, int], None]] = None,
        telemetry: Optional[Telemetry] = None,
    ):
        if not bundle.supports_paged:
            raise ValueError(
                f"family {bundle.cfg.family!r} has no paged serving path; "
                "use the dense cache (launch/serve.py default)"
            )
        self.bundle = bundle
        self.params = params
        if page_size is None:
            page_size = bundle.cfg.attention.block_kv
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        self.page_size = int(page_size)
        self.max_batch = int(max_batch)
        self.num_pages = int(num_pages)
        if max_seq_len is None:
            self.max_pages_per_seq = self.num_pages - 1
            self.max_seq_len = self.max_pages_per_seq * self.page_size
        else:
            if max_seq_len < 1:
                raise ValueError(f"max_seq_len must be >= 1, got {max_seq_len}")
            self.max_pages_per_seq = min(
                math.ceil(max_seq_len / self.page_size), self.num_pages - 1
            )
            self.max_seq_len = int(max_seq_len)

        if chunked_prefill and not bundle.supports_chunked_prefill:
            raise ValueError(
                f"family {bundle.cfg.family!r} has no chunked-prefill path; "
                "pass chunked_prefill=False"
            )
        self.chunked_prefill = bool(chunked_prefill)
        if prefill_chunk is None:
            prefill_chunk = 8 * self.page_size
        if prefill_chunk < 1 or prefill_chunk % self.page_size:
            raise ValueError(
                f"prefill_chunk ({prefill_chunk}) must be a positive "
                f"multiple of page_size ({self.page_size}); page-aligned "
                "chunk boundaries are what make chunked prefill bit-exact"
            )
        self.prefill_chunk = int(prefill_chunk)
        if prefix_cache and not self.chunked_prefill:
            raise ValueError(
                "prefix_cache requires chunked_prefill: cached page contents "
                "are defined by the chunk-exact convention, which the "
                "token-by-token decode path does not produce"
            )

        self._policy = get_scheduler(scheduler)
        if prefill_batch is None:
            prefill_batch = self.max_batch
        if prefill_batch < 1:
            raise ValueError(f"prefill_batch must be >= 1, got {prefill_batch}")
        self.prefill_batch = min(int(prefill_batch), self.max_batch)
        if step_token_budget is not None and step_token_budget < self.page_size:
            raise ValueError(
                f"step_token_budget ({step_token_budget}) below page_size "
                f"({self.page_size}) could never grant a page-aligned chunk"
            )
        self.step_token_budget = (
            None if step_token_budget is None else int(step_token_budget)
        )
        self.preemption = bool(preemption)
        if preempt_patience < 1:
            raise ValueError(
                f"preempt_patience must be >= 1, got {preempt_patience}"
            )
        self.preempt_patience = int(preempt_patience)

        if (trim_high is None) != (trim_low is None):
            raise ValueError("trim_high and trim_low must be set together")
        if trim_high is not None:
            if not prefix_cache:
                raise ValueError("cache trimming requires prefix_cache=True")
            if not 0.0 <= trim_low <= trim_high <= 1.0:
                raise ValueError(
                    f"need 0 <= trim_low <= trim_high <= 1, got "
                    f"{trim_low}/{trim_high}"
                )
            allocatable = self.num_pages - 1
            self._trim_high_pages = int(trim_high * allocatable)
            self._trim_low_pages = int(trim_low * allocatable)
        else:
            self._trim_high_pages = None
            self._trim_low_pages = None

        if temperature < 0.0:
            raise ValueError(f"temperature must be >= 0, got {temperature}")
        if top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {top_k}")
        self.temperature = float(temperature)
        # top_k beyond the vocabulary is "no truncation", not a trace error
        self.top_k = min(int(top_k), bundle.cfg.vocab_size)
        self._base_key = jax.random.PRNGKey(sample_seed)

        if speculate < 0:
            raise ValueError(f"speculate must be >= 0, got {speculate}")
        if speculate > 0 and not self.chunked_prefill:
            raise ValueError(
                "speculate requires chunked_prefill: the verify call "
                "rides the decode-phase cursor convention, which the "
                "token-by-token mode does not maintain"
            )
        self.speculate = int(speculate)
        self._drafter = get_drafter(draft) if self.speculate > 0 else None
        # speculation tallies (stats()["spec"]; zeros when speculate=0)
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.spec_rollbacks = 0
        self.spec_verify_steps = 0

        self.cache_dtype = resolve_pool_dtype(cache_dtype)
        self.mesh = mesh
        pool_kw = {} if mesh is None else {"mesh": mesh}
        self.pool = bundle.init_paged_cache(
            self.num_pages, self.page_size, dtype=self.cache_dtype, **pool_kw
        )
        self.telemetry = telemetry
        tel_metrics = telemetry.metrics if telemetry is not None else None
        self.allocator = PageAllocator(self.num_pages, metrics=tel_metrics)
        self.prefix_cache = (
            RadixPrefixCache(
                self.allocator, self.page_size, metrics=tel_metrics
            )
            if prefix_cache else None
        )
        self.page_table = np.full(
            (self.max_batch, self.max_pages_per_seq), NULL_PAGE, np.int32
        )
        self._slots: List[Optional[Request]] = [None] * self.max_batch
        self._next_token = np.zeros((self.max_batch,), np.int32)
        self.waiting: deque = deque()
        self.finished: Dict[int, Request] = {}
        self.steps = 0
        self.preemptions = 0
        self.trimmed_pages = 0
        # per-step token-spend accounting (decode rows + real prefill
        # tokens): the observable the step_token_budget contract is
        # asserted against (tests/test_scheduler.py)
        self.last_step_tokens = 0
        self.max_step_tokens = 0
        self._req_counter = 0

        if pipeline_depth < 0:
            raise ValueError(
                f"pipeline_depth must be >= 0, got {pipeline_depth}"
            )
        self.pipeline_depth = int(pipeline_depth)
        self.on_token = on_token
        self.cancellations = 0
        # steps dispatched but not yet retired (oldest first); bounded by
        # pipeline_depth at the end of every step().
        self._inflight: deque = deque()
        # The decode-step token feed is split: slots whose next input the
        # host KNOWS (teacher forcing, replay, prompt starts) read
        # _next_token under _next_known; the rest read the on-device
        # _next_dev - the previous step's sampled output, never read back
        # on the per-step path (see _compose_feed).
        self._next_known = np.ones((self.max_batch,), bool)
        self._next_dev = jnp.zeros((self.max_batch,), jnp.int32)

        step = bundle.paged_serve_step
        sampled = self.temperature > 0.0
        sampler = (
            _make_sampler(self.temperature, self.top_k, self._base_key)
            if sampled else None
        )

        if sampled:
            def _device_step(params, token, pos, pool, table, rids, idxs):
                logits, new_pool = step(params, token, pos, pool, table)
                return sampler(logits, rids, idxs), new_pool
        else:
            def _device_step(params, token, pos, pool, table):
                logits, new_pool = step(params, token, pos, pool, table)
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return nxt, new_pool

        # Sharded serving (mesh given): the device bodies run under a
        # fully-MANUAL shard_map - no SPMD partitioner ever touches them.
        # The body's pool boundary is the ONLY distributed code: every
        # kv-head-sharded leaf is all-gathered to full width on entry and
        # the updated pool is sliced back to this device's shard on exit
        # (``_wrap_pool_io``), so the interior is the UNMODIFIED 1-device
        # step computation - verbatim, with parameter-like inputs - and
        # its outputs (tokens AND page bytes) are bitwise those of the
        # 1-device serve.  Annotation-based GSPMD cannot make that
        # promise: its partitioner re-splits even replicated-annotated
        # contractions (partial sums + all-reduce change summation
        # order), and module-dependent fusion drifts near-zero values by
        # an ulp - both observed and bisected on this backend.  jit
        # in/out NamedShardings place the pool at its kv-head sharding on
        # both sides so donation survives; everything host-produced
        # (tokens/pos/tables/sample rows) and params stay replicated.
        # kwargs stay empty on the 1-device path.
        step_jit, prefill_jit = {}, {}
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from repro.runtime.paged_cache import pool_pspecs

            repl = NamedSharding(mesh, P())
            pshard = pool_shardings(mesh, self.pool, bundle.cfg.n_kv_heads)
            prepl = jax.tree.map(lambda _: repl, self.params)
            # place the replicated weights once (a no-op when the caller
            # created them there), not on every step's jit boundary
            self.params = jax.device_put(self.params, prepl)
            extra = (repl, repl) if sampled else ()
            step_jit = dict(
                in_shardings=(prepl, repl, repl, pshard, repl) + extra,
                out_shardings=(repl, pshard),
            )
            prefill_jit = dict(
                in_shardings=(
                    (prepl, repl, repl, repl, repl, pshard, repl) + extra
                ),
                out_shardings=(repl, pshard),
            )
            rp = P()
            pspec = pool_pspecs(mesh, self.pool, bundle.cfg.n_kv_heads)
            pr_spec = jax.tree.map(lambda _: rp, self.params)
            extra_sp = (rp, rp) if sampled else ()
            wrap = self._make_pool_io(mesh, pspec)
            _device_step = jax.shard_map(
                wrap(_device_step, 3), mesh=mesh,
                in_specs=(pr_spec, rp, rp, pspec, rp) + extra_sp,
                out_specs=(rp, pspec), check_vma=False,
            )

        # donate the pool: the update is a scatter of B tokens into a pool
        # that can dwarf device memory if double-buffered.
        self._step_fn = jax.jit(_device_step, donate_argnums=(3,), **step_jit)

        if self.chunked_prefill:
            pstep = bundle.paged_prefill_step

            if sampled:
                def _device_prefill(params, tokens, start, kv_len, last, pool,
                                    table, rids, idxs):
                    logits, new_pool = pstep(
                        params, tokens, start, kv_len, last, pool, table
                    )
                    return sampler(logits, rids, idxs), new_pool
            else:
                def _device_prefill(params, tokens, start, kv_len, last, pool,
                                    table):
                    logits, new_pool = pstep(
                        params, tokens, start, kv_len, last, pool, table
                    )
                    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    return nxt, new_pool

            if mesh is not None:
                _device_prefill = jax.shard_map(
                    wrap(_device_prefill, 5), mesh=mesh,
                    in_specs=(
                        (pr_spec, rp, rp, rp, rp, pspec, rp) + extra_sp
                    ),
                    out_specs=(rp, pspec), check_vma=False,
                )
            self._prefill_fn = jax.jit(
                _device_prefill, donate_argnums=(5,), **prefill_jit
            )

        # Speculative verify: ONE widened decode call running K+1 chained
        # decode sub-steps (feed token + K drafts) under lax.scan - each
        # sub-step is the UNMODIFIED ``paged_serve_step``, so every
        # position's logits (and its KV append, quantized requant
        # included) are bitwise the plain decode path's.  Before each
        # sub-step the ONE page its write touches is snapshotted
        # (``capture_pages``); after the accepted count m is computed on
        # device, a reverse scan restores the pre-images of sub-steps
        # >= m (``restore_pages``) - so rejected drafts leave ZERO trace
        # in the pool and rollback never allocates or frees a page.
        # Per-sub-step masking mirrors the batched decode's: inactive
        # (row, position)s get a nulled table row, writing to null page
        # 0 exactly like non-decoding slots do in the plain call.
        if self.speculate > 0:
            n_spec = self.speculate + 1
            psz = self.page_size

            def _device_verify(params, tokens, pos0, active, pool, table,
                               *extra):
                def body(pool, i):
                    act = active[:, i]
                    tbl = jnp.where(act[:, None], table, NULL_PAGE)
                    pos = jnp.where(act, pos0 + i, 0)
                    phys = touched_pages(tbl, pos, psz)
                    pre = capture_pages(pool, phys)
                    logits, pool = step(
                        params, tokens[:, i], pos, pool, tbl
                    )
                    if sampled:
                        rids, idx0 = extra
                        g = sampler(logits, rids, idx0 + i)
                    else:
                        g = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    return pool, (g, phys, pre)

                pool, (gs, physs, pres) = jax.lax.scan(
                    body, pool, jnp.arange(n_spec)
                )
                g = jnp.swapaxes(gs, 0, 1)                      # (B, K+1)
                # accepted count: 1 (the regular feed token always
                # stands) + the longest draft prefix matching the
                # model's own per-position choice; 0 for rows that were
                # not decoding at all this step.
                match = active[:, 1:] & (tokens[:, 1:] == g[:, :-1])
                m = 1 + jnp.cumprod(
                    match.astype(jnp.int32), axis=1
                ).sum(axis=1)
                m = jnp.where(active[:, 0], m, 0).astype(jnp.int32)

                def rbody(pool, x):
                    i, phys, pre = x
                    return restore_pages(pool, phys, pre, i >= m), None

                pool, _ = jax.lax.scan(
                    rbody, pool, (jnp.arange(n_spec), physs, pres),
                    reverse=True,
                )
                # next on-device feed: the last ACCEPTED position's output
                nxt = jnp.take_along_axis(
                    g, jnp.clip(m - 1, 0, n_spec - 1)[:, None], axis=1
                )[:, 0]
                return (nxt, g, m), pool

            verify_jit = {}
            if mesh is not None:
                verify_jit = dict(
                    in_shardings=(
                        (prepl, repl, repl, repl, pshard, repl) + extra
                    ),
                    out_shardings=((repl, repl, repl), pshard),
                )
                _device_verify = jax.shard_map(
                    wrap(_device_verify, 4), mesh=mesh,
                    in_specs=(pr_spec, rp, rp, rp, pspec, rp) + extra_sp,
                    out_specs=((rp, rp, rp), pspec), check_vma=False,
                )
            self._verify_fn = jax.jit(
                _device_verify, donate_argnums=(4,), **verify_jit
            )

    # ------------------------------------------------------- device calls --

    @staticmethod
    def _make_pool_io(mesh, pspec):
        """Build the manual-TP pool boundary for a shard_map body: every
        leaf whose PartitionSpec trails in ``"model"`` is all-gathered to
        full width on entry (tiled, device order == kv-head order - pure
        data movement) and the updated pool is sliced back to this
        device's shard on exit.  Optimization barriers at both boundaries
        keep the interior an isolated fusion island, so it compiles
        exactly like the 1-device program whose subgraph it is.  With a
        replicated-fallback pool (no "model" entries) the wrapper is the
        identity and the body IS the 1-device program."""
        from repro.runtime.paged_cache import model_axis_size

        msize = model_axis_size(mesh)
        sharded = {name for name, s in pspec.items() if s[-1] == "model"}

        def expand(pool):
            if not sharded:
                return pool
            pool = {
                name: (
                    jax.lax.all_gather(
                        x, "model", axis=x.ndim - 1, tiled=True
                    ) if name in sharded else x
                )
                for name, x in pool.items()
            }
            return jax.lax.optimization_barrier(pool)

        def contract(pool):
            if not sharded:
                return pool
            pool = jax.lax.optimization_barrier(pool)
            out = {}
            for name, x in pool.items():
                if name in sharded:
                    size = x.shape[-1] // msize
                    idx = jax.lax.axis_index("model") * size
                    x = jax.lax.dynamic_slice_in_dim(
                        x, idx, size, x.ndim - 1
                    )
                out[name] = x
            return out

        def wrap(fn, pool_argnum):
            def wrapped(*args):
                args = list(args)
                args[pool_argnum] = expand(args[pool_argnum])
                out, new_pool = fn(*args)
                return out, contract(new_pool)
            return wrapped

        return wrap

    def _device_call(self, fn, *args):
        """Invoke a jitted step.  With a mesh, the (first-call) trace runs
        with the launch-sharding thread-local mesh CLEARED: the body sits
        inside a fully-manual shard_map, where the generic GSPMD hooks
        (``shard()`` constraints, the row-parallel psum matmul) must not
        fire - the model code then traces exactly as it does on one
        device, which is the point (see the ``mesh`` arg doc).
        Steady-state calls just hit the jit cache."""
        if self.mesh is None:
            return fn(*args)
        from repro.launch.sharding import get_mesh, set_mesh

        prev_mesh = get_mesh()
        set_mesh(None)
        try:
            return fn(*args)
        finally:
            set_mesh(prev_mesh)

    # ------------------------------------------------------------- queue --

    def submit(
        self, prompt, max_new_tokens: int, req_id: Optional[int] = None,
        *, tenant: str = DEFAULT_TENANT, priority: str = "throughput",
    ) -> Request:
        """Enqueue a request; admission happens inside :meth:`step`.

        ``tenant`` and ``priority`` (one of
        ``scheduler.PRIORITY_CLASSES``) attribute the request for
        quota-aware policies (``scheduler="tenant"``) and per-tenant
        telemetry; policies that do not read them behave exactly as
        before.  They shape latency only - never output bits.

        Raises ValueError immediately for requests that could NEVER be
        served - ``len(prompt) + max_new_tokens`` beyond ``max_seq_len`` or
        beyond the pool's page capacity - instead of letting them wedge the
        queue behind an unsatisfiable head forever.
        """
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if not tenant or not isinstance(tenant, str):
            raise ValueError(f"tenant must be a non-empty string: {tenant!r}")
        if priority not in PRIORITY_CLASSES:
            raise ValueError(
                f"priority must be one of {PRIORITY_CLASSES}, got {priority!r}"
            )
        if req_id is None:
            req_id = self._req_counter
        self._req_counter = max(self._req_counter + 1, req_id + 1)
        r = Request(
            req_id=req_id, prompt=prompt, max_new_tokens=max_new_tokens,
            tenant=tenant, priority=priority,
        )
        if r.total_len > self.max_seq_len:
            raise ValueError(
                f"request needs {len(prompt)} prompt + {max_new_tokens} new "
                f"= {r.total_len} positions > max_seq_len {self.max_seq_len}"
                "; it can never be served under the bounded page table"
            )
        need = r.pages_needed(self.page_size)
        if need > self.max_pages_per_seq:
            raise ValueError(
                f"request needs {need} pages > pool capacity "
                f"{self.max_pages_per_seq}"
            )
        r.submit_step = self.steps
        self.waiting.append(r)
        if self.telemetry is not None:
            self.telemetry.on_submit(
                r.req_id, self.steps, tenant=r.tenant, priority=r.priority
            )
        return r

    # ------------------------------------------------------- policy view --

    def _view(self, r: Request) -> RequestView:
        if r.state == RUNNING and self.chunked_prefill:
            rem_prefill = max(len(r.prompt) - r.prefill_pos, 0)
        elif r.state == RUNNING:
            rem_prefill = max(len(r.prompt) - 1 - r.cursor, 0)
        else:
            rem_prefill = len(r.prompt)
        return RequestView(
            req_id=r.req_id,
            prompt_len=len(r.prompt),
            remaining_prefill=rem_prefill,
            remaining_decode=max(r.max_new_tokens - len(r.generated), 0),
            submit_step=r.submit_step,
            admit_step=r.admit_step if r.state == RUNNING else -1,
            slot=r.slot,
            pages_needed=r.pages_needed(self.page_size),
            preempt_count=r.preempt_count,
            preempt_step=r.preempt_step,
            pending_tokens=r.pending,
            tenant=r.tenant,
            priority=r.priority,
        )

    # --------------------------------------------------------- admission --

    def _admit_one(self, r: Request) -> str:
        """Try to place one waiting request; returns "admitted",
        "no_slot", or "no_pages".  Grants a free slot + the worst-case
        page count, charging only NON-SHARED pages when the prefix cache
        is enabled (matched prefix pages are referenced, not copied;
        refcount-0 cache pages are evicted on demand)."""
        slot = next(
            (i for i, s in enumerate(self._slots) if s is None), None
        )
        if slot is None:
            return "no_slot"
        nodes = []
        if self.prefix_cache is not None:
            # cap at len(prompt)-1: the last prompt position is always
            # computed (its logits are the first generated token), and
            # the final/partial page stays private (copy-on-write).
            nodes = self.prefix_cache.match(
                r.prompt, max_tokens=len(r.prompt) - 1
            )
        need_new = r.pages_needed(self.page_size) - len(nodes)
        if self.prefix_cache is not None:
            short = need_new - self.allocator.free_pages
            # Evict only when eviction actually covers the shortfall:
            # otherwise admission fails regardless and the cache would
            # be stripped of resident prefixes for nothing.
            if 0 < short <= self.prefix_cache.evictable_pages:
                self.prefix_cache.evict(short)
        pages = self.allocator.alloc(need_new)
        if pages is None:
            if nodes:
                self.prefix_cache.release(nodes)
            return "no_pages"
        self.waiting.remove(r)
        if self.prefix_cache is not None:
            self.prefix_cache.record_match(
                r.prompt, nodes, max_tokens=len(r.prompt) - 1
            )
        r.state = RUNNING
        r.slot = slot
        r.pages = pages
        r.prefix_nodes = nodes
        r.cached_len = len(nodes) * self.page_size
        r.admit_step = self.steps
        r.blocked_steps = 0
        self._slots[slot] = r
        row = self.page_table[slot]
        row[:] = NULL_PAGE
        shared = [n.page for n in nodes]
        row[: len(shared)] = shared
        row[len(shared): len(shared) + len(pages)] = pages
        if self.chunked_prefill:
            r.prefill_pos = r.cached_len
            r.cursor = len(r.prompt)     # decode starts after the prompt
        else:
            r.prefill_pos = len(r.prompt)  # unused in this mode
            r.cursor = 0
            self._next_token[slot] = r.prompt[0]
            self._next_known[slot] = True
        if self.telemetry is not None:
            self.telemetry.on_admit(
                r.req_id, self.steps, resumed=r.preempt_count > 0
            )
        return "admitted"

    def _admit_pass(self) -> Optional[Request]:
        """Admit everything the policy can place this step; returns the
        first page-blocked candidate (the preemption trigger) or None.

        Free pages never increase within a pass (admission only consumes;
        eviction proceeds are immediately allocated), so a candidate that
        failed on pages is skipped for the rest of the pass instead of
        re-walking the prefix trie on every rescan."""
        blocked: Optional[Request] = None
        page_failed: set = set()
        while self.waiting:
            order = self._policy.plan_admission(
                [self._view(r) for r in self.waiting],
                [self._view(r) for r in self._slots if r is not None],
                now=self.steps,
            )
            by_id = {r.req_id: r for r in self.waiting}
            admitted = False
            for v in order:
                if v.req_id in page_failed:
                    continue
                r = by_id[v.req_id]
                status = self._admit_one(r)
                if status == "admitted":
                    admitted = True
                    break
                if status == "no_slot":
                    return blocked
                page_failed.add(r.req_id)
                if blocked is None:
                    blocked = r
                if self._policy.hol_blocking:
                    # intentional head-of-line blocking: nothing behind
                    # the blocked head is admitted this step
                    return blocked
            if not admitted:
                return blocked
        return blocked

    def _try_admit(self) -> None:
        blocked = self._admit_pass()
        if blocked is None:
            return
        blocked.blocked_steps += 1
        if self.telemetry is not None:
            self.telemetry.on_admission_blocked(self.steps)
        if (not self.preemption
                or blocked.blocked_steps < self.preempt_patience):
            return
        if blocked.preempt_count > 0:
            # Anti-thrash: a request that was itself paged out never
            # triggers another preemption - it waits for running work to
            # drain.  Without this, two requests that cannot coexist
            # ping-pong preempting each other forever.
            return
        victim_view = self._policy.choose_victim(
            [self._view(r) for r in self._slots if r is not None],
            now=self.steps,
        )
        if victim_view is None:
            return
        victim = next(
            (s for s in self._slots
             if s is not None and s.req_id == victim_view.req_id), None
        )
        if victim is None:
            return
        # Preempt only when paging the victim out can actually unblock the
        # starved candidate: its owned pages are freed or become
        # refcount-0 cache pages, both reclaimable by admission.
        avail = self.allocator.free_pages + len(victim.pages)
        if self.prefix_cache is not None:
            avail += self.prefix_cache.evictable_pages
        if avail < blocked.pages_needed(self.page_size):
            return
        # Drain-and-replan: preemption must record the victim's generated
        # tokens for REPLAY - the one plan decision that depends on token
        # VALUES, not counts - so the pipeline synchronizes here before
        # the victim is paged out.  (The preempt TRIGGER itself is
        # count-based and fired without a readback.)
        self.drain()
        # the drain itself can FINISH the victim (a retiring speculative
        # verify's accepted count reached max_new_tokens) - its pages are
        # then already free and paging it out would double-release
        if victim.state == RUNNING:
            self._preempt(victim)
        blocked.blocked_steps = 0
        self._admit_pass()

    # -------------------------------------------------- page-out / finish --

    def _release_slot(self, r: Request) -> None:
        """Free the request's slot and pages.  With the prefix cache
        enabled, its prefill-written FULL prompt pages are donated (their
        contents are a pure function of the token prefix - the chunk-exact
        convention; decode-written pages never qualify and are freed)."""
        row = self.page_table[r.slot]
        if self.prefix_cache is not None:
            n_share = min(r.prefill_pos, len(r.prompt)) // self.page_size
            adopted = set(
                self.prefix_cache.insert(
                    r.prompt[: n_share * self.page_size],
                    list(row[:n_share]),
                )
            )
            if r.prefix_nodes:
                self.prefix_cache.release(r.prefix_nodes)
            self.allocator.free([p for p in r.pages if p not in adopted])
        else:
            self.allocator.free(r.pages)
        row[:] = NULL_PAGE
        self._slots[r.slot] = None
        r.pages = []
        r.prefix_nodes = []
        r.slot = -1

    def _preempt(self, r: Request) -> None:
        """Page a running request out: donate/free its pages, record its
        generated tokens for replay, and re-queue it at the BACK of the
        waiting queue (a paged-out straggler yields its seniority)."""
        self._release_slot(r)
        # A twice-preempted request may be preempted mid-replay: keep the
        # not-yet-replayed recorded suffix (generated[i] == replay[i]
        # bitwise while replaying, so this is a pure extension).
        r.replay = r.generated + r.replay[len(r.generated):]
        r.generated = []
        r.state = WAITING
        r.preempt_count += 1
        r.preempt_step = self.steps
        r.prefill_pos = 0
        r.cursor = 0
        r.cached_len = 0
        r.blocked_steps = 0
        self.preemptions += 1
        self.waiting.append(r)
        if self.telemetry is not None:
            self.telemetry.on_preempt(r.req_id, self.steps, tenant=r.tenant)

    def _finish(self, r: Request, *, step: Optional[int] = None) -> None:
        """Finish a request.  ``step`` overrides the stamp for finishes
        decided at RETIREMENT (a speculative verify's accepted count):
        the step that DISPATCHED the verify, so the stamp matches what
        the synchronous engine records for the same serve."""
        self._release_slot(r)
        r.state = FINISHED
        r.finish_step = self.steps if step is None else step
        self.finished[r.req_id] = r
        if self.telemetry is not None:
            self.telemetry.on_finish(
                r.req_id, r.finish_step, tenant=r.tenant
            )

    def _account_step_tokens(self, n: int) -> None:
        self.last_step_tokens = int(n)
        if n > self.max_step_tokens:
            self.max_step_tokens = int(n)

    # ------------------------------------------------- retire / cancel --

    @_drain_point
    def _retire_one(self) -> None:
        """Materialize the OLDEST in-flight step's sampled tokens: fill
        the placeholder ``generated`` entries recorded at dispatch and
        fire ``on_token`` in dispatch order (prefill completions first,
        then decode rows - the synchronous emission order).  This is the
        ONLY per-token device readback in the engine; in async mode it
        runs AFTER the next step was dispatched, so the block overlaps
        device execution instead of serializing with it.

        Retirement is also THE first-token stamp site: ``gen_idx == 0``
        of a request not yet stamped sets ``first_token_step`` to the
        step that DISPATCHED the token (``st.step_no``), so the value is
        identical across pipeline modes and a preempted-then-resumed
        request keeps its ORIGINAL stamp (preemption never clears it -
        TTFT measures submit to first emission, not to re-admission;
        tests/test_telemetry.py pins both)."""
        st = self._inflight.popleft()
        emitted = 0
        by_tenant: Dict[str, int] = {}
        for tok_dev, emits in (
            (st.prefill_tok, st.prefill_emits),
            (st.decode_tok, st.decode_emits),
        ):
            if not emits:
                continue
            vals = np.asarray(tok_dev)
            for r, gen_idx, row in emits:
                tok = int(vals[row])
                r.generated[gen_idx] = tok
                r.pending -= 1
                emitted += 1
                by_tenant[r.tenant] = by_tenant.get(r.tenant, 0) + 1
                if gen_idx == 0 and r.first_token_step < 0:
                    r.first_token_step = st.step_no
                    if self.telemetry is not None:
                        self.telemetry.on_first_token(
                            r.req_id, r.submit_step, st.step_no,
                            tenant=r.tenant,
                        )
                if self.on_token is not None:
                    self.on_token(r, gen_idx, tok)
        # Speculative verifies: materialize each row's accepted count m
        # and its m verifier tokens.  Cursor advance, generated growth,
        # and the finish decision were all DEFERRED from dispatch (m was
        # device-resident); they happen here, and rollback is already
        # done - the device restored every rejected position's page
        # bytes before this step's pool left the verify call.
        if st.spec_rows:
            spec_vals = np.asarray(st.decode_tok)
            spec_ms = np.asarray(st.verify_m)
        for r, slot, k in st.spec_rows:
            m = int(spec_ms[slot])
            gen_idx0 = len(r.generated)
            for j in range(m):
                tok = int(spec_vals[slot, j])
                r.generated.append(tok)
                emitted += 1
                by_tenant[r.tenant] = by_tenant.get(r.tenant, 0) + 1
                if self.on_token is not None:
                    self.on_token(r, gen_idx0 + j, tok)
            r.cursor += m
            r.verifying = False
            self.spec_accepted += m - 1
            rb_pages = 0
            if m <= k:
                # at least one draft rejected: its pages were restored
                self.spec_rollbacks += 1
                c0 = r.cursor - m
                rb_pages = len({
                    (c0 + j) // self.page_size for j in range(m, k + 1)
                })
            if self.telemetry is not None:
                self.telemetry.on_spec_retire(k, m - 1, rb_pages)
            if len(r.generated) >= r.max_new_tokens:
                self._finish(r, step=st.step_no)
        if emitted and self.telemetry is not None:
            self.telemetry.on_tokens_emitted(emitted, by_tenant=by_tenant)

    def _retire_backlog(self) -> None:
        """Retire down to ``pipeline_depth`` steps in flight (the tail of
        every :meth:`step`; depth 0 = fully synchronous)."""
        while len(self._inflight) > self.pipeline_depth:
            self._retire_one()

    @_drain_point
    def drain(self) -> None:
        """Retire EVERY in-flight step - the pipeline barrier.  Legal
        sync points: stream boundaries (:meth:`run_to_completion`,
        benchmark edges), value-dependent plan decisions (preemption
        replay recording in :meth:`_try_admit`), and :meth:`cancel`."""
        while self._inflight:
            self._retire_one()

    def cancel(self, req_id: int) -> bool:
        """Cancel a request mid-stream (client disconnect).

        WAITING requests leave the queue; a RUNNING request's slot is
        released through the same path as preemption/finish - private
        pages freed, prefill-written full prompt pages DONATED to the
        prefix cache (their bytes are already valid shared state by the
        chunk-exact purity argument, whether or not the client stayed to
        see the stream).  Safe while a step is in flight: the pipeline is
        drained first, so no in-flight emission can touch the request
        after it is released, and page recycling stays ordered behind the
        dispatched pool updates by donation threading.  Returns True if
        the request was live (waiting or running), False otherwise."""
        for r in self.waiting:
            if r.req_id == req_id:
                self.waiting.remove(r)
                r.state = CANCELLED
                r.finish_step = self.steps
                self.cancellations += 1
                if self.telemetry is not None:
                    self.telemetry.on_cancel(req_id, self.steps)
                return True
        r = next(
            (s for s in self._slots
             if s is not None and s.req_id == req_id), None
        )
        if r is None:
            return False
        self.drain()
        if r.state != RUNNING:
            # the drain retired a speculative verify whose accepted count
            # FINISHED the request - the cancel lost the race; its slot
            # and pages were already released through _finish.
            return False
        self._release_slot(r)
        r.state = CANCELLED
        r.finish_step = self.steps
        self.cancellations += 1
        if self.telemetry is not None:
            self.telemetry.on_cancel(req_id, self.steps)
        return True

    # ---------------------------------------------------------- trimming --

    def _maybe_trim(self) -> None:
        """Background watermark trim: when live pages exceed the high
        watermark, evict refcount-0 cache pages down toward the low one.
        The probe is O(1) (allocator counter + the cached
        ``evictable_pages``), so this runs every step for free."""
        if self._trim_high_pages is None or self.prefix_cache is None:
            return
        if self.allocator.live_pages <= self._trim_high_pages:
            return
        excess = self.allocator.live_pages - self._trim_low_pages
        n = min(excess, self.prefix_cache.evictable_pages)
        if n > 0:
            self.trimmed_pages += self.prefix_cache.evict(n)

    # -------------------------------------------------------------- step --

    @property
    def num_running(self) -> int:
        return sum(s is not None for s in self._slots)

    @property
    def idle(self) -> bool:
        """No queued work, no live request, and nothing still in flight.

        Counting ``_inflight`` makes ``while not eng.idle: eng.step()``
        loops mode-agnostic: once the last live request finishes, the
        next ``step()`` finds no dispatchable work and fully drains (see
        :meth:`step`), so the loop exits only after every placeholder
        has been retired into real tokens."""
        return (not self.waiting and self.num_running == 0
                and not self._inflight)

    @staticmethod
    def _sample_rows(pairs, n: int):
        """(req_id, token index) int32 rows for the jitted sampler; rows
        with ``pairs[i] is None`` (dead) get zeros - their samples are
        never read."""
        rids = np.zeros((n,), np.int32)
        idxs = np.zeros((n,), np.int32)
        for i in range(min(len(pairs), n)):
            if pairs[i] is not None:
                rids[i], idxs[i] = pairs[i]
        return jnp.asarray(rids), jnp.asarray(idxs)

    def _run_prefill(self, plan, st: _InflightStep):
        """One BATCHED prefill call: each planned request contributes one
        chunk row (its own start offset, valid length, and page-table
        row); rows and tails are padded to the static (prefill_batch,
        prefill_chunk) grid and pad positions write to the null page.

        The call is DISPATCHED, never synced: first-token values of
        prompt-completing rows are recorded into ``st`` as placeholder
        emissions, and rows without a host-known resume value get their
        device output scattered into ``_next_dev`` (an eager gather/
        scatter - data dependence, no readback) so the same step's decode
        can consume them.

        Returns ``(tokens_spent, completed)``: the total REAL prefill
        tokens advanced (the spend the policy budgeted for) and the
        requests whose prompt finished inside this call - the budget
        accounting in :meth:`step` needs both."""
        by_id = {
            r.req_id: r for r in self._slots
            if r is not None and r.prefill_pos < len(r.prompt)
        }
        rows = []
        for rid, grant in plan:
            r = by_id.get(rid)
            if r is None or grant < 1 or len(rows) >= self.prefill_batch:
                continue
            rows.append((r, min(grant, len(r.prompt) - r.prefill_pos)))
        if not rows:
            return 0, []
        pb, cs = self.prefill_batch, self.prefill_chunk
        tokens = np.zeros((pb, cs), np.int32)
        start = np.zeros((pb,), np.int32)
        kv_len = np.zeros((pb,), np.int32)
        last = np.zeros((pb,), np.int32)
        table = np.full((pb, self.max_pages_per_seq), NULL_PAGE, np.int32)
        for i, (r, real) in enumerate(rows):
            c0 = r.prefill_pos
            tokens[i, :real] = r.prompt[c0: c0 + real]
            start[i] = c0
            kv_len[i] = c0 + real
            last[i] = real - 1
            table[i] = self.page_table[r.slot]
        args = [
            self.params,
            jnp.asarray(tokens), jnp.asarray(start), jnp.asarray(kv_len),
            jnp.asarray(last), self.pool, jnp.asarray(table),
        ]
        if self.temperature > 0.0:
            args.extend(self._sample_rows(
                [(r.req_id, len(r.generated)) for r, _ in rows], pb
            ))
        first, self.pool = self._device_call(self._prefill_fn, *args)
        st.prefill_tok = first
        completed = []
        scatter: List[Tuple[int, int]] = []   # (slot, output row)
        for i, (r, real) in enumerate(rows):
            r.prefill_pos += real
            if r.prefill_pos >= len(r.prompt):
                # this chunk contained the last prompt token; its logits
                # row is the first generated token - TTFT is now.
                slot = r.slot
                gen_idx = len(r.generated)
                r.generated.append(None)       # filled at retirement
                r.pending += 1
                if r.replay:
                    # resume replay: feed the recorded emission (bit-equal
                    # to the recomputed token) so the stream stays
                    # consistent - a host-KNOWN value.
                    self._next_token[slot] = r.replay[0]
                    self._next_known[slot] = True
                else:
                    self._next_known[slot] = False
                    scatter.append((slot, i))
                st.prefill_emits.append((r, gen_idx, i))
                completed.append(r)
                if len(r.generated) >= r.max_new_tokens:
                    self._finish(r)
        if scatter:
            slots = jnp.asarray([s for s, _ in scatter], jnp.int32)
            srcs = jnp.asarray([i for _, i in scatter], jnp.int32)
            self._next_dev = self._next_dev.at[slots].set(first[srcs])
        return sum(real for _, real in rows), completed

    def _plan_speculation(self, dec, prefill_spent: int):
        """Host-side draft proposal + policy grant for this step's decode
        rows: returns ``[(request, k, draft tokens)]`` for the rows that
        run a K-draft verify this step (absent rows keep plain decode).

        Draft CONTENT is latency-only by construction - accepted tokens
        matched the model's own choice and rejected writes are restored
        on device - so none of the host heuristics here (history
        materialization, the async ``skip`` guess, budget clipping) can
        change output bits.  Eligibility: at least 2 tokens remaining
        (a K-speculation emits up to K+1, and the final token needs no
        page backing, so K <= remaining-1 keeps conservative admission's
        page bound intact - speculation NEVER allocates), and not inside
        teacher-forced replay (replayed values are already known)."""
        cands, drafts = [], {}
        for r in dec:
            remaining = r.max_new_tokens - len(r.generated)
            if remaining < 2 or len(r.generated) < len(r.replay):
                continue
            # propose from the MATERIALIZED history; placeholders whose
            # values are still on device (async) are skipped over by the
            # proposer (a guess-on-a-guess; still bit-safe, see above)
            hist = r.prompt + r.generated[:len(r.generated) - r.pending]
            d = self._drafter.propose(
                hist, min(self.speculate, remaining - 1), skip=r.pending
            )
            if d:
                cands.append(r)
                drafts[r.req_id] = [int(t) for t in d]
        if not cands:
            return []
        left = None
        if self.step_token_budget is not None:
            left = max(
                self.step_token_budget - len(dec) - prefill_spent, 0
            )
        grants = self._policy.plan_speculation(
            [self._view(r) for r in cands],
            k=self.speculate, budget_left=left,
        )
        by_id = {r.req_id: r for r in cands}
        out = []
        for rid, g in grants:
            r = by_id.get(rid)
            if r is None or g < 1:
                continue
            d = drafts[r.req_id][:g]
            if d:
                out.append((r, len(d), d))
        return out

    def _compose_feed(self):
        """This step's decode token inputs: host-known values (teacher
        forcing, replay, prompt starts) overriding the on-device sampled
        tokens from the previous dispatch.  A fused int32 select
        (:data:`_select_i32`) - exact by construction - so both pipeline
        modes feed bit-identical vectors through the SAME jitted decode
        program, and the host never touches a sampled value here.  Host
        buffers are copied before crossing to device: the backend may
        alias numpy memory zero-copy, and ``_next_token``/``_next_known``
        mutate while async steps are still in flight (the page tables get
        the same fresh-copy treatment at dispatch - the double-buffering
        that makes overlap safe)."""
        host = np.array(self._next_token)
        if self._next_known.all():
            return jnp.asarray(host)
        return _select_i32(np.array(self._next_known), host, self._next_dev)

    def step(self) -> int:
        """One engine step: host PLAN (trim, admission, policy decisions,
        page-table assembly), device DISPATCH (the policy's batched
        prefill plan + ONE batched decode step, both enqueued without a
        sync), optimistic host advance (cursors and ``generated`` COUNTS
        - placeholder values), then retirement of any step beyond
        ``pipeline_depth`` (depth 0 materializes this very step - the
        synchronous mode).

        Returns the number of requests that were live this step.  ``steps``
        advances on every call (it is the engine's scheduling clock, used
        for arrival/admission timestamps); the device calls are skipped
        when no request needs them.
        """
        tel = self.telemetry
        t0 = tel.clock() if tel is not None else 0.0
        self._maybe_trim()
        self._try_admit()
        # telemetry phase stamps: plan = trim + admission (host-only);
        # dispatch = per-step table/feed assembly + enqueueing the jitted
        # calls; retire = materializing steps beyond pipeline_depth.
        t_plan = tel.clock() if tel is not None else 0.0
        live = [r for r in self._slots if r is not None]
        if not live:
            self._account_step_tokens(0)   # idle tick spends nothing
            # nothing to dispatch means nothing to overlap with: drain
            # fully so ``while not eng.idle: eng.step()`` terminates with
            # every placeholder retired (see :meth:`idle`)
            self.drain()
            if tel is not None:
                tel.end_step(self, t0, t_plan, t_plan, 0)
            self.steps += 1
            return 0
        n_live = len(live)

        st = _InflightStep(step_no=self.steps)
        if self.chunked_prefill:
            prefilling = [
                r for r in self._slots
                if r is not None and r.prefill_pos < len(r.prompt)
            ]
            # rows with a speculative verify still in flight sit this
            # plan out (their cursor/counts are frozen until retirement)
            # and spend no budget - they are neither prefill nor decode
            n_verifying = sum(
                1 for r in self._slots if r is not None and r.verifying
            )
            n_decode = n_live - len(prefilling) - n_verifying
            prefill_spent, completed = 0, []
            if prefilling:
                plan = self._policy.plan_prefill(
                    [self._view(r) for r in prefilling],
                    n_decode=n_decode,
                    budget=self.step_token_budget,
                    chunk=self.prefill_chunk,
                    page_size=self.page_size,
                    max_rows=self.prefill_batch,
                )
                if plan:
                    prefill_spent, completed = self._run_prefill(plan, st)
            dec = [
                r for r in self._slots
                if r is not None and r.prefill_pos >= len(r.prompt)
                and not r.verifying
            ]
            if self.step_token_budget is not None:
                # Budget accounting for prefill-COMPLETING rows: the policy
                # charged n_decode (counted BEFORE the prefill call) plus
                # the prefill grants, but a row whose prompt finished
                # inside this step's prefill call has just joined ``dec``
                # and would decode an extra, never-budgeted token this same
                # step.  Defer the first decode of just enough of them
                # (latest grants first) to the next step - bit-preserving,
                # since scheduling is latency-only; decode rows counted by
                # the plan are never deferred (decode latency stays the
                # protected quantity).
                over = len(dec) + prefill_spent - self.step_token_budget
                if over > 0:
                    in_dec = {r.req_id for r in dec}
                    deferrable = [
                        r.req_id for r in completed if r.req_id in in_dec
                    ]
                    defer = set(deferrable[max(len(deferrable) - over, 0):])
                    if defer:
                        dec = [r for r in dec if r.req_id not in defer]
            # speculation grants: drafted AFTER prefill/decode spend is
            # known, so draft tokens only ever consume LEFTOVER budget
            spec_plan = (
                self._plan_speculation(dec, prefill_spent)
                if self.speculate > 0 and dec else []
            )
            n_draft = sum(k for _, k, _ in spec_plan)
            self._account_step_tokens(len(dec) + prefill_spent + n_draft)
            if not dec:
                # prefill-only step: completions (if any, all budget
                # -deferred) still owe their first-token emissions.
                if st.prefill_emits:
                    self._inflight.append(st)
                elif prefill_spent == 0:
                    # Only verifying rows are live and NOTHING was
                    # dispatched this step: with pipeline_depth >= 1 the
                    # count-based backlog alone would never retire the
                    # in-flight verifies, so force retirement here to
                    # make those rows dispatchable again (the verify
                    # analogue of the idle-tick drain above).
                    self.drain()
                t_disp = tel.clock() if tel is not None else 0.0
                self._retire_backlog()
                if tel is not None:
                    tel.end_step(self, t0, t_plan, t_disp, n_live)
                self.steps += 1
                return n_live
            # decode view of the table: slots not decoding THIS step
            # (empty, still-prefilling, or budget-deferred) are nulled so
            # the batched scatter cannot touch their pages.
            dec_slots = {r.slot for r in dec}
            table = np.array(self.page_table)
            for i in range(self.max_batch):
                if i not in dec_slots:
                    table[i, :] = NULL_PAGE
        else:
            dec = live
            spec_plan = []   # speculation requires chunked_prefill
            # fresh copy per dispatch: the live table mutates under
            # later admissions while this step may still be in flight
            table = np.array(self.page_table)
            self._account_step_tokens(len(dec))

        pos = np.zeros((self.max_batch,), np.int32)
        for r in dec:
            pos[r.slot] = r.cursor

        feed = self._compose_feed()
        if spec_plan:
            # widened dispatch: ONE verify call carries every decode row
            # - speculating rows with their K drafts, the rest as k=0
            # rows active only at position 0 (their sub-step 0 IS the
            # plain decode, bit-for-bit; positions 1.. write null page 0
            # and are restored like any rejected draft).
            drafts = np.zeros((self.max_batch, self.speculate), np.int32)
            active = np.zeros((self.max_batch, self.speculate + 1), bool)
            for r in dec:
                active[r.slot, 0] = True
            for r, k, d in spec_plan:
                drafts[r.slot, :k] = d
                active[r.slot, 1:1 + k] = True
            tok = jnp.concatenate(
                [feed[:, None], jnp.asarray(drafts)], axis=1
            )
            args = [self.params, tok, jnp.asarray(pos),
                    jnp.asarray(active), self.pool, jnp.asarray(table)]
            if self.temperature > 0.0:
                pairs = [None] * self.max_batch
                for r in dec:
                    pairs[r.slot] = (r.req_id, len(r.generated))
                args.extend(self._sample_rows(pairs, self.max_batch))
            (nxt, gtok, m_dev), self.pool = self._device_call(
                self._verify_fn, *args
            )
            st.decode_tok = gtok
            st.verify_m = m_dev
            self.spec_proposed += n_draft
            self.spec_verify_steps += len(spec_plan)
            if tel is not None:
                tel.on_spec_dispatch(len(spec_plan), n_draft)
        else:
            args = [self.params, feed, jnp.asarray(pos), self.pool,
                    jnp.asarray(table)]
            if self.temperature > 0.0:
                pairs = [None] * self.max_batch
                for r in dec:
                    pairs[r.slot] = (r.req_id, len(r.generated))
                args.extend(self._sample_rows(pairs, self.max_batch))
            nxt, self.pool = self._device_call(self._step_fn, *args)
            st.decode_tok = nxt
        # keep each decoding slot's sampled output resident on device for
        # the NEXT step's feed; non-decoding slots retain their value.
        # On a verify dispatch ``nxt`` is the last ACCEPTED position's
        # output - exactly the token the plain path would have fed next.
        mask = np.zeros((self.max_batch,), bool)
        for r in dec:
            mask[r.slot] = True
        self._next_dev = _select_i32(mask, nxt, feed)

        # optimistic host advance: cursors, COUNTS, finish decisions -
        # all deterministic at dispatch; values arrive at retirement.
        # Speculating rows are the exception: their advance depends on
        # the device-resident accepted count, so they freeze until
        # retirement (``verifying``) instead of advancing optimistically.
        spec_ids = {r.req_id for r, _, _ in spec_plan}
        for r in dec:
            if r.req_id in spec_ids:
                r.verifying = True
                self._next_known[r.slot] = False
                continue
            p = r.cursor
            r.cursor += 1
            if not self.chunked_prefill and p + 1 < len(r.prompt):
                self._next_token[r.slot] = r.prompt[p + 1]   # teacher forcing
                self._next_known[r.slot] = True
                continue
            slot = r.slot
            gen_idx = len(r.generated)
            r.generated.append(None)           # filled at retirement
            r.pending += 1
            if gen_idx < len(r.replay):
                self._next_token[slot] = r.replay[gen_idx]
                self._next_known[slot] = True
            else:
                self._next_known[slot] = False   # value lives in _next_dev
            st.decode_emits.append(
                (r, gen_idx, (slot, 0) if spec_plan else slot)
            )
            if len(r.generated) >= r.max_new_tokens:
                self._finish(r)
        for r, k, _ in spec_plan:
            st.spec_rows.append((r, r.slot, k))
        self._inflight.append(st)
        t_disp = tel.clock() if tel is not None else 0.0
        self._retire_backlog()
        if tel is not None:
            tel.end_step(self, t0, t_plan, t_disp, n_live)
        self.steps += 1
        return n_live

    def run_to_completion(self, max_steps: int = 100_000) -> Dict[int, Request]:
        """Drive :meth:`step` until queue and slots drain.

        ``max_steps`` bounds THIS call (the engine's lifetime counter keeps
        running across calls)."""
        start = self.steps
        while not self.idle:
            if self.steps - start >= max_steps:
                raise RuntimeError(f"engine did not drain in {max_steps} steps")
            self.step()
        self.drain()   # stream boundary: materialize trailing emissions
        return self.finished

    # ------------------------------------------------------------- stats --

    def metrics_snapshot(self) -> Optional[dict]:
        """The metrics-registry scrape payload (counters / gauges /
        histograms as plain JSON-serializable dicts) - the surface a
        future HTTP front end serves.  None when telemetry (or its
        metrics layer) is off."""
        if self.telemetry is None:
            return None
        return self.telemetry.metrics_snapshot()

    def stats(self) -> dict:
        """Schema-versioned snapshot (``STATS_SCHEMA``; key catalog in
        runtime/README.md).  Every key is always present -
        ``prefix_cache`` is None when the cache is disabled - and
        :meth:`EngineReplicaGroup.stats` aggregates the SAME keys, so
        consumers never branch on engine-vs-group shape."""
        out = {
            "schema": STATS_SCHEMA,
            "steps": self.steps,
            "running": self.num_running,
            "waiting": len(self.waiting),
            "finished": len(self.finished),
            "free_pages": self.allocator.free_pages,
            "live_pages": self.allocator.live_pages,
            "cache_bytes": paged_bytes(self.pool),
            "cache_bytes_per_device": paged_bytes_per_device(self.pool),
            "page_size": self.page_size,
            "pool_dtype": pool_dtype_name(self.cache_dtype),
            "chunked_prefill": self.chunked_prefill,
            "scheduler": self._policy.name,
            "prefill_batch": self.prefill_batch,
            "step_token_budget": self.step_token_budget,
            "preemptions": self.preemptions,
            "trimmed_pages": self.trimmed_pages,
            "temperature": self.temperature,
            "last_step_tokens": self.last_step_tokens,
            "max_step_tokens": self.max_step_tokens,
            "pipeline_depth": self.pipeline_depth,
            "inflight": len(self._inflight),
            "cancellations": self.cancellations,
            "speculate": self.speculate,
            # always present (zeros when speculation is off) so stats
            # consumers never branch on configuration
            "spec": {
                "proposed": self.spec_proposed,
                "accepted": self.spec_accepted,
                "rollbacks": self.spec_rollbacks,
                "verify_steps": self.spec_verify_steps,
            },
            "prefix_cache": (
                None if self.prefix_cache is None
                else self.prefix_cache.stats()
            ),
        }
        return out


#: Replica-routing modes for :class:`EngineReplicaGroup.submit`.
ROUTING_MODES = ("affinity", "least", "rr")


class EngineReplicaGroup:
    """Data-parallel paged serving over a 2-D ``(data, model)`` mesh.

    One :class:`ServeEngine` replica per ``data``-axis row, each serving
    from its OWN page pool sharded over that row's ``model`` devices
    (``ServeEngine(mesh=...)``); requests from one logical queue are
    routed across replicas.  Replicas share nothing on device - sharding
    the pools over ``model`` is the tensor-parallel dimension, replicas
    over ``data`` the throughput dimension - so per-request streams stay
    bit-identical to a single-engine serve (routing only changes which
    pool a request's pages live in, and decode reads only the request's
    own page-table row).

    ``routing`` picks the placement policy:

      * ``"affinity"`` (default): probe every replica's radix prefix trie
        (:meth:`RadixPrefixCache.probe_len`, a pure read) and send the
        request to the replica holding the longest cached prefix of its
        prompt; with no cached prefix anywhere (or the prefix cache off)
        fall back to least-loaded.  A burst sharing a system prompt lands
        on the replica that already holds those pages instead of
        re-prefilling them per replica (benchmarks/scheduler_burst.py).
      * ``"least"``: least-loaded (fewest waiting + running requests),
        ties broken by a rotating cursor.  When loads are equal this IS
        round-robin - a burst submitted up front deals ``i::n`` exactly -
        but after a :meth:`cancel` or an early finish the next requests
        fill the gap instead of blindly continuing the rotation.
      * ``"rr"``: strict rotation regardless of load (the legacy deal;
        kept for schedule reproduction).

    Routing never changes streams: request ids are group-global, so the
    sampled tokens of request N are identical wherever it lands.

    The group exposes the subset of the engine surface the launcher needs
    (submit / step / run_to_completion / stats); per-request bookkeeping
    stays on the underlying :class:`Request` objects.
    """

    def __init__(self, bundle, params, mesh, *, telemetry=None,
                 routing: str = "affinity", **engine_kwargs):
        from jax.sharding import Mesh

        names = mesh.axis_names
        if not set(names) <= {"data", "model"}:
            raise ValueError(
                f"EngineReplicaGroup needs a (data, model) mesh; got axes "
                f"{names}"
            )
        shape = dict(mesh.shape)
        n_data = int(shape.get("data", 1))
        n_model = int(shape.get("model", 1))
        # row-major (data, model) device grid regardless of axis order
        # (np.array: a host object grid, not a device readback - the
        # np.asarray/np.array convention tests/test_async_guard.py keys on)
        devs = np.array(mesh.devices)
        if names and names[0] == "model" and "data" in names:
            devs = devs.T
        devs = devs.reshape(n_data, n_model)
        self.meshes = [
            Mesh(devs[i].reshape(n_model), ("model",)) for i in range(n_data)
        ]
        # per-replica telemetry children share the group's tracer (events
        # carry the replica index -> separate Chrome processes) but keep
        # their own metrics registries; metrics_snapshot() aggregates.
        self.telemetry = telemetry
        self.engines = [
            ServeEngine(
                bundle, params, mesh=m,
                telemetry=(
                    None if telemetry is None else telemetry.for_replica(i)
                ),
                **engine_kwargs,
            )
            for i, m in enumerate(self.meshes)
        ]
        if routing not in ROUTING_MODES:
            raise ValueError(
                f"routing must be one of {ROUTING_MODES}, got {routing!r}"
            )
        self.routing = routing
        self._rr = 0
        self._req_counter = 0
        self._owner: Dict[int, ServeEngine] = {}

    @property
    def n_replicas(self) -> int:
        return len(self.engines)

    # ----------------------------------------------------------- routing --

    def _load(self, eng: ServeEngine) -> int:
        """A replica's outstanding work in requests: queued + occupying a
        slot.  Counts, not token volumes - cheap, and proportional enough
        to spot the post-cancel imbalance strict rotation ignores."""
        return len(eng.waiting) + eng.num_running

    def _pick_least_loaded(self, cands: List[int]) -> int:
        """Least-loaded among candidate replica indices; ties broken by a
        rotating cursor so equal-load routing degenerates to round-robin
        (the deal existing schedules are pinned to)."""
        lo = min(self._load(self.engines[i]) for i in cands)
        tied = [i for i in cands if self._load(self.engines[i]) == lo]
        pick = min(tied, key=lambda i: (i - self._rr) % len(self.engines))
        self._rr = pick + 1
        return pick

    def _route(self, prompt) -> ServeEngine:
        n = len(self.engines)
        if self.routing == "rr":
            pick = self._rr % n
            self._rr += 1
            return self.engines[pick]
        cands = list(range(n))
        if self.routing == "affinity":
            probes = [
                0 if e.prefix_cache is None
                else e.prefix_cache.probe_len(prompt)
                for e in self.engines
            ]
            best = max(probes)
            if best > 0:
                cands = [i for i in cands if probes[i] == best]
        return self.engines[self._pick_least_loaded(cands)]

    def submit(self, prompt, max_new_tokens: int, *,
               tenant: str = DEFAULT_TENANT,
               priority: str = "throughput") -> Request:
        """Route one request from the logical queue (see class doc for
        the routing modes).  Request ids are GROUP-global - the ids a
        single engine serving the same submission order would assign - so
        per-(req id, token index) sampling keys (and with them sampled
        streams) are routing-invariant, and :meth:`cancel` can address a
        request without knowing which replica owns it."""
        prompt = [int(t) for t in prompt]
        eng = self._route(prompt)
        rid = self._req_counter
        self._req_counter += 1
        r = eng.submit(
            prompt, max_new_tokens, req_id=rid,
            tenant=tenant, priority=priority,
        )
        self._owner[r.req_id] = eng
        return r

    def cancel(self, req_id: int) -> bool:
        """Cancel a request on whichever replica owns it (see
        :meth:`ServeEngine.cancel`)."""
        eng = self._owner.get(req_id)
        return False if eng is None else eng.cancel(req_id)

    def drain(self) -> None:
        """Pipeline barrier across every replica (stream boundary)."""
        for e in self.engines:
            e.drain()

    @property
    def idle(self) -> bool:
        return all(e.idle for e in self.engines)

    def step(self) -> int:
        """Advance EVERY replica one engine step - idle ones included, so
        each replica's scheduling clock keeps the per-engine invariant
        (``steps`` advances on every call) and arrival-paced drivers that
        poll ``steps`` never stall on an early-drained replica.

        With async engines (``pipeline_depth >= 1``) the replicas advance
        INDEPENDENTLY rather than lock-step: each per-replica call
        dispatches without a readback barrier, so one replica's retirement
        overlaps the others' device execution instead of serializing the
        round."""
        return sum(e.step() for e in self.engines)

    def run_to_completion(self, max_steps: int = 100_000):
        """Drive all replicas INTERLEAVED until every queue drains (the
        data-parallel dimension overlaps; wall-clock ~= the slowest
        replica, not the sum).  ``max_steps`` bounds this call per
        replica clock."""
        start = max(e.steps for e in self.engines)
        while not self.idle:
            if max(e.steps for e in self.engines) - start >= max_steps:
                raise RuntimeError(
                    f"replica group did not drain in {max_steps} steps"
                )
            self.step()
        self.drain()
        out: Dict[tuple, Request] = {}
        for i, e in enumerate(self.engines):
            for rid, r in e.finished.items():
                out[(i, rid)] = r
        return out

    def metrics_snapshot(self) -> Optional[dict]:
        """Cross-replica aggregated metrics snapshot (counters and
        histograms summed, gauges summed except ``*_max``); None when
        the group was built without telemetry."""
        if self.telemetry is None:
            return None
        return self.telemetry.metrics_snapshot()

    def stats(self) -> dict:
        """True aggregation of :meth:`ServeEngine.stats` over replicas -
        the SAME schema-versioned shared keys (tallies summed, clocks and
        per-device peaks maxed, uniform config passed through; see
        ``_STATS_SUM`` / ``_STATS_MAX`` / ``_STATS_CONFIG``), plus
        ``replicas`` and the per-replica dicts under ``engines``."""
        per = [e.stats() for e in self.engines]
        out = {"schema": STATS_SCHEMA, "replicas": len(per)}
        for key in _STATS_SUM:
            out[key] = sum(s[key] for s in per)
        for key in _STATS_MAX:
            out[key] = max(s[key] for s in per)
        for key in _STATS_CONFIG:
            out[key] = per[0][key]
        out["spec"] = {
            k: sum(s["spec"][k] for s in per) for k in per[0]["spec"]
        }
        out["prefix_cache"] = (
            None if per[0]["prefix_cache"] is None
            else {
                k: sum(s["prefix_cache"][k] for s in per)
                for k in per[0]["prefix_cache"]
            }
        )
        out["engines"] = per
        return out
