"""Paged-KV subsystem: allocator invariants, paged decode kernel vs oracles,
paged-vs-dense serving equivalence, ragged-tail shift conventions."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import adversarial_inputs as adv
import repro.kernels as K
from adversarial_inputs import adversarial_case  # noqa: F401
from repro.core import FP16, FP32, F64, blocked_attention, naive_attention
from repro.core.numerics import rmse
from repro.runtime import (
    NULL_PAGE,
    PageAllocator,
    ServeEngine,
    dense_greedy_reference,
    gather_pages,
)

I = dict(interpret=True)
BETA = 0.9375


# ------------------------------------------------------------- allocator --

class TestPageAllocator:
    def test_null_page_reserved_and_capacity(self):
        a = PageAllocator(8)
        got = a.alloc(7)
        assert got is not None and NULL_PAGE not in got
        assert sorted(got) == list(range(1, 8))
        assert a.alloc(1) is None  # exhausted, all-or-nothing

    def test_alloc_is_all_or_nothing(self):
        a = PageAllocator(5)
        assert a.alloc(5) is None          # only 4 allocatable
        assert a.free_pages == 4           # failed alloc changed nothing
        p = a.alloc(4)
        a.free(p)
        assert a.free_pages == 4 and a.live_pages == 0

    def test_double_and_foreign_free_raise(self):
        a = PageAllocator(4)
        p = a.alloc(2)
        a.free(p)
        with pytest.raises(ValueError):
            a.free(p)                      # double free
        with pytest.raises(ValueError):
            a.free([NULL_PAGE])            # the sink is never freeable

    def test_free_and_live_partition_pages(self):
        a = PageAllocator(9)
        p1, p2 = a.alloc(3), a.alloc(2)
        a.free(p1)
        assert a.free_pages + a.live_pages == 8
        assert set(p2).isdisjoint(a._free)


# ---------------------------------------------------- paged decode kernel --

def _paged_setup(key, b, kvh, g, d, kv_lens, page, extra_pages=2):
    """Build a contiguous cache AND an equivalent shuffled-page pool."""
    ks = jax.random.split(key, 4)
    mp = max(math.ceil(l / page) for l in kv_lens) + 1
    s2 = mp * page
    kv_len = jnp.asarray(kv_lens, jnp.int32)
    mask = (jnp.arange(s2) < kv_len[:, None])[:, None, :, None]
    # Draw K/V at float32 EXPLICITLY: the physical pool below is float32,
    # and under the suite's jax_enable_x64 a default-dtype draw is float64
    # - the contiguous kernel would then consume f64->f16 single-rounded
    # inputs while the paged kernel consumes f64->f32->f16 double-rounded
    # pool bytes, and the bit-equality pins compare different INPUTS
    # (~1e-3 of elements flip by one f16 ulp), not different kernels.
    q = jax.random.normal(ks[0], (b, kvh, g, d), jnp.float32) + 1.0
    kc = jnp.where(
        mask, jax.random.normal(ks[1], (b, kvh, s2, d), jnp.float32) + 2.0, 0.0
    )
    vc = jnp.where(
        mask, jax.random.normal(ks[2], (b, kvh, s2, d), jnp.float32), 0.0
    )

    # scatter the logical blocks into a SHUFFLED physical pool
    n_pages = 1 + b * mp + extra_pages
    rng = np.random.default_rng(0)
    ids = rng.permutation(np.arange(1, n_pages))
    table = np.full((b, mp), NULL_PAGE, np.int32)
    k_pool = np.zeros((n_pages, page, kvh, d), np.float32)
    v_pool = np.zeros((n_pages, page, kvh, d), np.float32)
    nxt = 0
    kcn = np.moveaxis(np.asarray(kc), 2, 1)  # (B, S2, KVH, D)
    vcn = np.moveaxis(np.asarray(vc), 2, 1)
    for bi in range(b):
        for j in range(math.ceil(kv_lens[bi] / page)):
            pid = int(ids[nxt]); nxt += 1
            table[bi, j] = pid
            k_pool[pid] = kcn[bi, j * page : (j + 1) * page]
            v_pool[pid] = vcn[bi, j * page : (j + 1) * page]
    return (
        q, kc, vc, kv_len,
        jnp.asarray(k_pool), jnp.asarray(v_pool), jnp.asarray(table),
    )


@pytest.mark.parametrize("kv_lens", [[300, 77], [128, 512], [255, 256]])
@pytest.mark.parametrize("beta", [0.0, BETA])
def test_paged_kernel_bitmatches_contiguous_kernel(kv_lens, beta, rng):
    """Same math, different memory layout: the paged kernel must equal the
    contiguous decode kernel BIT-FOR-BIT (page == block granularity; dead
    pages are skipped exactly like dead blocks)."""
    b, kvh, g, d, page = 2, 2, 4, 64, 128
    q, kc, vc, kv_len, kp, vp, table = _paged_setup(
        rng, b, kvh, g, d, kv_lens, page
    )
    got = K.pasa_paged_decode(
        q, kp, vp, table, kv_len, beta=beta, policy=FP16, **I
    )
    want = K.pasa_decode(
        q, kc, vc, kv_len, beta=beta, policy=FP16, block_kv=page, **I
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("kv_lens", [[300, 77]])
def test_paged_kernel_vs_xla_fallback_and_gold(kv_lens, rng):
    """fp16 policy, shuffled page table: kernel ~ XLA fallback ~ fp64 exact
    attention within the fp16 tolerances used in test_kernels.py."""
    b, kvh, g, d, page = 2, 2, 4, 64, 128
    q, kc, vc, kv_len, kp, vp, table = _paged_setup(
        rng, b, kvh, g, d, kv_lens, page
    )
    got = K.pasa_paged_decode(
        q, kp, vp, table, kv_len, beta=BETA, policy=FP16, **I
    )
    xla = K.pasa_paged_decode(
        q, kp, vp, table, kv_len, beta=BETA, policy=FP16, use_kernel=False
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(xla, np.float32),
        atol=3e-3, rtol=3e-2,
    )
    # paper's metric: RMSE against exact fp64 attention on the valid prefix
    for bi in range(b):
        L = int(kv_len[bi])
        gold = naive_attention(
            q[bi : bi + 1].astype(jnp.float64),
            kc[bi : bi + 1, :, :L].astype(jnp.float64),
            vc[bi : bi + 1, :, :L].astype(jnp.float64),
            dtype=jnp.float64,
        )
        assert rmse(got[bi : bi + 1], gold) < 0.03
        assert rmse(xla[bi : bi + 1], gold) < 0.03


@pytest.mark.parametrize("quantized", [False, True])
def test_emulated_fp16_kernels_vs_xla_fallback(quantized, monkeypatch, rng):
    """Compiled (Mosaic) kernels hold fp16 values in f32 containers and
    round at the policy's storage points (kernels/fp16.py); interpret mode
    keeps native fp16.  Run the emulated bodies in interpret mode: paged
    decode and paged prefill stay within the kernel-vs-fallback tolerance
    of the native kernels."""
    import importlib

    from repro.kernels import fp16
    from repro.kernels.ops import _check_quant

    # the package re-exports functions under these module names
    pasa_paged_decode = importlib.import_module(
        "repro.kernels.pasa_paged_decode"
    )
    pasa_paged_prefill = importlib.import_module(
        "repro.kernels.pasa_paged_prefill"
    )
    from repro.runtime.paged_cache import quantize_kv_page

    b, kvh, g, d, page = 2, 2, 4, 64, 128
    q, kc, vc, kv_len, kp, vp, table = _paged_setup(
        rng, b, kvh, g, d, [300, 77], page
    )
    quant = {}
    if quantized:
        valid = jnp.ones(kp.shape[:2], bool)
        kp, ksc, ksh = quantize_kv_page(kp, valid, jnp.int8)
        vp, vsc, vsh = quantize_kv_page(vp, valid, jnp.int8)
        quant = _check_quant(kp, (ksc, ksh, vsc, vsh))
    else:
        kp, vp = kp.astype(jnp.float16), vp.astype(jnp.float16)
    emulated = lambda emulate_f16: fp16.KernelNumerics(emulate_f16=True)
    for mod in (pasa_paged_decode, pasa_paged_prefill):
        monkeypatch.setattr(mod, "KernelNumerics", emulated)
    pol = dict(
        stat_dtype=FP16.stat_dtype, acc_dtype=FP16.acc_dtype,
        score_dtype=FP16.score_dtype, out_dtype=FP16.out_dtype,
        deq_dtype=FP16.input_dtype, interpret=True,
    )
    inva = BETA / (1.0 - BETA)

    got = pasa_paged_decode.paged_decode_kernel_call.__wrapped__(
        q.astype(jnp.float16), kp, vp, table, kv_len,
        inva=inva, beta=BETA, **pol, **quant,
    )
    want = K.pasa_paged_decode(
        q, kp, vp, table, kv_len, beta=BETA, policy=FP16, use_kernel=False,
        **quant,
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=3e-3, rtol=3e-2,
    )

    cs = 64
    qp = jax.random.normal(rng, (b, kvh * g, cs, d), jnp.float32)
    start = kv_len - cs
    got = pasa_paged_prefill.paged_prefill_kernel_call.__wrapped__(
        qp.astype(jnp.float16), kp, vp, table, start, kv_len,
        inva=inva, beta=BETA, block_q=cs, **pol, **quant,
    )
    want = K.pasa_paged_prefill(
        qp, kp, vp, table, start, kv_len, beta=BETA, policy=FP16,
        use_kernel=False, **quant,
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=3e-3, rtol=3e-2,
    )


def test_paged_xla_fallback_bitmatches_dense_xla(rng):
    """The gather fallback == blocked_attention on the contiguous cache,
    bit-for-bit, even though the paged view is longer (its trailing dead
    blocks contribute exactly zero under shift_mask_valid)."""
    b, kvh, g, d, page = 2, 2, 4, 32, 64
    q, kc, vc, kv_len, kp, vp, table = _paged_setup(
        rng, b, kvh, g, d, [100, 37], page, extra_pages=5
    )
    got = K.pasa_paged_decode(
        q, kp, vp, table, kv_len, beta=BETA, policy=FP16, use_kernel=False
    )
    want = blocked_attention(
        q, kc.astype(jnp.float16), vc.astype(jnp.float16),
        beta=BETA, policy=FP16, block_kv=page, causal=False,
        kv_len=kv_len.reshape(b, 1),
        use_gemm_shift=False, shift_mask_valid=True,
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --------------------------------------------- contiguous decode raggedness --

def test_decode_kernel_accepts_non_multiple_cache_len(rng):
    """S2 % block_kv != 0 pads internally instead of raising (the kv_len
    masking makes the zero tail inert)."""
    b, kvh, g, d = 2, 2, 4, 64
    ks = jax.random.split(rng, 3)
    s2 = 300  # not a multiple of 128
    kv_len = jnp.asarray([300, 77], jnp.int32)
    mask = (jnp.arange(s2) < kv_len[:, None])[:, None, :, None]
    q = jax.random.normal(ks[0], (b, kvh, g, d), jnp.float32) + 1.0
    kc = jnp.where(mask, jax.random.normal(ks[1], (b, kvh, s2, d), jnp.float32) + 2.0, 0.0)
    vc = jnp.where(mask, jax.random.normal(ks[2], (b, kvh, s2, d), jnp.float32), 0.0)
    got = K.pasa_decode(
        q, kc, vc, kv_len, beta=BETA, policy=FP16, block_kv=128, **I
    )
    # identical to explicitly pre-padded input
    pad = jnp.zeros((b, kvh, 384 - s2, d))
    got_pad = K.pasa_decode(
        q, jnp.concatenate([kc, pad], 2), jnp.concatenate([vc, pad], 2),
        kv_len, beta=BETA, policy=FP16, block_kv=128, **I
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(got_pad))
    for bi in range(b):
        L = int(kv_len[bi])
        gold = naive_attention(
            q[bi : bi + 1].astype(jnp.float64),
            kc[bi : bi + 1, :, :L].astype(jnp.float64),
            vc[bi : bi + 1, :, :L].astype(jnp.float64),
            dtype=jnp.float64,
        )
        assert rmse(got[bi : bi + 1], gold) < 0.03


def test_tail_shift_conventions_both_exact_and_close(rng):
    """Satellite: the two ragged-tail conventions - full-block mean
    (use_gemm_shift / plain algebraic) vs masked valid-column mean
    (shift_mask_valid, the decode-kernel semantics) - are BOTH exact softmax
    at fp64, and agree within the fp16 oracle tolerance on partial tails.
    The accepted fp16 cross-convention bound (RMSE < 2e-2, the
    test_kernels.py tolerance) is what makes Pallas-vs-XLA comparisons
    well-defined for tail blocks."""
    ks = jax.random.split(rng, 3)
    b, h, s2, d = 2, 2, 512, 32
    kv_len = jnp.asarray([300, 77], jnp.int32).reshape(b, 1)
    q = jax.random.normal(ks[0], (b, h, 1, d), jnp.float64) + 1.0
    kc = jax.random.normal(ks[1], (b, h, s2, d), jnp.float64) + 2.0
    vc = jax.random.normal(ks[2], (b, h, s2, d), jnp.float64)

    kw = dict(beta=BETA, block_kv=128, causal=False, kv_len=kv_len)
    # fp64: both conventions match exact attention on the valid prefix
    full = blocked_attention(q, kc, vc, policy=F64, use_gemm_shift=False, **kw)
    masked = blocked_attention(
        q, kc, vc, policy=F64, use_gemm_shift=False, shift_mask_valid=True,
        **kw,
    )
    for bi in range(b):
        L = int(kv_len[bi, 0])
        gold = naive_attention(
            q[bi : bi + 1], kc[bi : bi + 1, :, :L], vc[bi : bi + 1, :, :L],
            dtype=jnp.float64,
        )
        assert rmse(full[bi : bi + 1], gold) < 1e-11
        assert rmse(masked[bi : bi + 1], gold) < 1e-11

    # fp16: conventions differ only by tail-block rounding, within the
    # kernel-oracle tolerance
    full16 = blocked_attention(
        q, kc, vc, policy=FP16, use_gemm_shift=False, **kw
    )
    masked16 = blocked_attention(
        q, kc, vc, policy=FP16, use_gemm_shift=False, shift_mask_valid=True,
        **kw,
    )
    assert rmse(full16, masked16.astype(jnp.float32)) < 2e-2


def test_paged_layout_is_bit_stable_under_adversarial_inputs(
    adversarial_case, rng
):
    """The paged-vs-contiguous bit contract must survive the paper's
    failure generators, not just friendly gaussians: same math, different
    memory layout, identical bits even when the values are resonant /
    biased / heavy-tailed ('Is Flash Attention Stable?': layout-level
    divergence only shows under stress inputs)."""
    b, kvh, g, d, page = 2, 2, 4, 64, 128
    kv_lens = [300, 77]
    mp = max(math.ceil(length / page) for length in kv_lens) + 1
    s2 = mp * page
    kv_len = jnp.asarray(kv_lens, jnp.int32)
    q, kc, vc = adv.make_adversarial(
        adversarial_case, rng,
        q_shape=(b, kvh, g, d), kv_shape=(b, kvh, s2, d),
    )
    mask = (jnp.arange(s2) < kv_len[:, None])[:, None, :, None]
    kc = jnp.where(mask, kc, 0.0)
    vc = jnp.where(mask, vc, 0.0)
    # pack the logical blocks into a shuffled pool (same as _paged_setup)
    n_pages = 1 + b * mp + 2
    ids = np.random.default_rng(0).permutation(np.arange(1, n_pages))
    table = np.full((b, mp), NULL_PAGE, np.int32)
    k_pool = np.zeros((n_pages, page, kvh, d), np.float32)
    v_pool = np.zeros((n_pages, page, kvh, d), np.float32)
    nxt = 0
    kcn = np.moveaxis(np.asarray(kc), 2, 1)
    vcn = np.moveaxis(np.asarray(vc), 2, 1)
    for bi in range(b):
        for j in range(math.ceil(kv_lens[bi] / page)):
            pid = int(ids[nxt]); nxt += 1
            table[bi, j] = pid
            k_pool[pid] = kcn[bi, j * page:(j + 1) * page]
            v_pool[pid] = vcn[bi, j * page:(j + 1) * page]
    got = K.pasa_paged_decode(
        q, jnp.asarray(k_pool), jnp.asarray(v_pool), jnp.asarray(table),
        kv_len, beta=BETA, policy=FP32, **I,
    )
    want = K.pasa_decode(
        q, kc, vc, kv_len, beta=BETA, policy=FP32, block_kv=page, **I
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # fp32-statistics accuracy holds under stress too (fp16-statistics
    # accuracy under these inputs is characterized in test_kv_quant.py)
    if adversarial_case != "resonance_180":   # near-uniform attention
        for bi in range(b):                   # inflates relative rmse
            L = int(kv_len[bi])
            gold = naive_attention(
                q[bi:bi + 1].astype(jnp.float64),
                kc[bi:bi + 1, :, :L].astype(jnp.float64),
                vc[bi:bi + 1, :, :L].astype(jnp.float64),
                dtype=jnp.float64,
            )
            assert rmse(got[bi:bi + 1], gold) < 0.03, (adversarial_case, bi)


def test_stale_pages_cannot_leak(rng):
    """Page recycling without scrubbing: poisoning every invalid position
    with huge garbage leaves the masked-shift output untouched."""
    b, kvh, g, d, page = 1, 2, 4, 32, 64
    q, kc, vc, kv_len, kp, vp, table = _paged_setup(
        rng, b, kvh, g, d, [100], page
    )
    clean = K.pasa_paged_decode(
        q, kp, vp, table, kv_len, beta=BETA, policy=FP16, use_kernel=False
    )
    # poison all pool positions past kv_len (incl. unreferenced pages)
    pos_in_seq = np.full((kp.shape[0], page), 10**6, np.int64)
    tab = np.asarray(table)
    for j in range(tab.shape[1]):
        if tab[0, j] != NULL_PAGE:
            pos_in_seq[tab[0, j]] = j * page + np.arange(page)
    stale = jnp.asarray((pos_in_seq >= int(kv_len[0]))[..., None, None])
    kp2 = jnp.where(stale, 333.0, kp)
    vp2 = jnp.where(stale, -777.0, vp)
    dirty = K.pasa_paged_decode(
        q, kp2, vp2, table, kv_len, beta=BETA, policy=FP16, use_kernel=False
    )
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(dirty))
    # NON-FINITE garbage too: a recycled page may hold Inf/NaN (fp16
    # overflow debris from a previous request); masked p must be forced to
    # exactly 0 or e_cur * (p @ v) would 0*Inf-poison the accumulator.
    kp3 = jnp.where(stale, jnp.inf, kp)
    vp3 = jnp.where(stale, jnp.nan, vp)
    poisoned = K.pasa_paged_decode(
        q, kp3, vp3, table, kv_len, beta=BETA, policy=FP16, use_kernel=False
    )
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(poisoned))
    kern_clean = K.pasa_paged_decode(
        q, kp, vp, table, kv_len, beta=BETA, policy=FP16, **I
    )
    kern_poisoned = K.pasa_paged_decode(
        q, kp3, vp3, table, kv_len, beta=BETA, policy=FP16, **I
    )
    np.testing.assert_array_equal(
        np.asarray(kern_clean), np.asarray(kern_poisoned)
    )


# ------------------------------------------------------------------ engine --

@pytest.fixture(scope="module")
def tiny_bundle():
    from repro.configs import get_config
    from repro.models.model_zoo import build

    cfg = get_config("qwen3-4b").reduced()
    bundle = build(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    return bundle, params


def test_engine_continuous_batching_matches_dense(tiny_bundle):
    """Staggered ragged requests through the engine == dense-cache greedy
    decode, token-for-token; all pages return to the free list."""
    bundle, params = tiny_bundle
    rng = np.random.default_rng(1)
    vocab = bundle.cfg.vocab_size
    eng = ServeEngine(bundle, params, max_batch=2, num_pages=8, page_size=16)
    specs = [(5, 6), (11, 4), (7, 5), (3, 7)]  # (prompt_len, gen)
    prompts = [list(rng.integers(0, vocab, n)) for n, _ in specs]
    reqs = [eng.submit(prompts[i], specs[i][1]) for i in range(2)]
    mid = []
    while not eng.idle:
        eng.step()
        if eng.steps == 3:
            mid.append(eng.submit(prompts[2], specs[2][1]))
        if eng.steps == 5:
            mid.append(eng.submit(prompts[3], specs[3][1]))
    reqs += mid
    assert all(r.state == "finished" for r in reqs)
    # the two late requests were admitted mid-stream, strictly after submit 0
    assert all(r.admit_step > 0 for r in mid)
    for r in reqs:
        want = dense_greedy_reference(bundle, params, r.prompt, r.max_new_tokens)
        assert r.generated == want, (r.req_id, r.generated, want)
    st = eng.stats()
    assert st["live_pages"] == 0 and st["free_pages"] == 7


def test_engine_page_reuse_is_clean(tiny_bundle):
    """A request decoded on recycled (dirty) pages matches one decoded on a
    fresh pool - the no-scrub guarantee end-to-end."""
    bundle, params = tiny_bundle
    rng = np.random.default_rng(2)
    vocab = bundle.cfg.vocab_size
    pa = list(rng.integers(0, vocab, 9))
    pb = list(rng.integers(0, vocab, 6))

    eng = ServeEngine(bundle, params, max_batch=1, num_pages=2, page_size=16)
    eng.submit(pa, 5)
    eng.run_to_completion()          # dirties the single data page
    rb = eng.submit(pb, 5)
    eng.run_to_completion()

    fresh = ServeEngine(bundle, params, max_batch=1, num_pages=2, page_size=16)
    rf = fresh.submit(pb, 5)
    fresh.run_to_completion()
    assert rb.generated == rf.generated


def test_rejected_draft_debris_is_inert(tiny_bundle):
    """Speculative-decoding extension of the no-scrub guarantee: a serve
    that speculated (verify writes draft K/V beyond the accepted point,
    then rolls the page bytes back) must leave the pool in a state where
    (a) every page returns to the free list - rollback never leaks or
    double-frees - and (b) a follow-up request decoded on those recycled
    pages matches a fresh-pool serve: no rejected-draft byte survives to
    be attended."""
    bundle, params = tiny_bundle
    pa = [3, 5, 7, 9] * 6            # repetitive: drafts + rollbacks
    pb = [11, 12, 13] * 4
    eng = ServeEngine(
        bundle, params, max_batch=1, num_pages=6, page_size=8,
        max_seq_len=48, prefill_chunk=16, speculate=3,
    )
    eng.submit(list(pa), 8)
    eng.run_to_completion()          # dirties pages with verify traffic
    assert eng.stats()["spec"]["verify_steps"] >= 1
    assert eng.allocator.free_pages == eng.num_pages - 1   # conservation
    rb = eng.submit(list(pb), 6)
    eng.run_to_completion()

    fresh = ServeEngine(
        bundle, params, max_batch=1, num_pages=6, page_size=8,
        max_seq_len=48, prefill_chunk=16,
    )
    rf = fresh.submit(list(pb), 6)
    fresh.run_to_completion()
    assert rb.generated == rf.generated


def test_evicted_prefix_pages_are_reused_cleanly(tiny_bundle):
    """Stale-page immunity through the prefix-cache lifecycle: pages
    donated to the radix cache, LRU-evicted under admission pressure, and
    recycled into a NEW request's page table still hold the old request's
    K/V debris - the valid-column masking must keep it inert, and the
    evicted branch must be recomputed (bit-identically), not served."""
    from repro.runtime import chunked_cold_reference

    bundle, params = tiny_bundle
    rng = np.random.default_rng(9)
    vocab = bundle.cfg.vocab_size
    pa = list(rng.integers(0, vocab, 17))
    pb = list(rng.integers(0, vocab, 17))

    # 3 allocatable pages; each request needs all 3, so every admission
    # after the first must first evict the previous donation.
    eng = ServeEngine(
        bundle, params, max_batch=1, num_pages=4, page_size=8,
        max_seq_len=24, prefix_cache=True,
    )
    ra = eng.submit(pa, 3)
    eng.run_to_completion()                     # donates pa's 2 full pages
    assert eng.prefix_cache.cached_pages == 2
    rb = eng.submit(pb, 3)                      # unrelated: evicts both and
    eng.run_to_completion()                     # recycles the dirty pages
    assert eng.prefix_cache.stats()["evictions"] == 2
    assert rb.generated == chunked_cold_reference(
        bundle, params, pb, 3, page_size=8
    )
    # pa again: its branch is gone, so this is a recompute on pages now
    # dirty with pb's K/V - and it must reproduce the original cold serve.
    ra2 = eng.submit(pa, 3)
    eng.run_to_completion()
    assert ra2.cached_len == 0
    assert ra2.generated == ra.generated
    assert eng.prefix_cache.stats()["evictions"] == 4


def test_engine_admission_is_conservative(tiny_bundle):
    """A request whose worst case cannot fit the free pool waits; one that
    can never fit the pool at all is rejected at submit."""
    bundle, params = tiny_bundle
    eng = ServeEngine(bundle, params, max_batch=2, num_pages=3, page_size=16)
    with pytest.raises(ValueError):
        eng.submit(list(range(1, 30)), 16)   # needs 3 pages > 2 allocatable
    r1 = eng.submit([1, 2, 3], 20)           # 23 -> 2 pages: takes the pool
    r2 = eng.submit([4, 5], 10)              # 1 page: must wait for r1
    eng.step()
    assert r1.state == "running" and r2.state == "waiting"
    eng.run_to_completion()
    assert r2.state == "finished" and r2.admit_step >= r1.finish_step


def test_gather_pages_roundtrip(rng):
    pool = jax.random.normal(rng, (5, 4, 6), jnp.float32)
    table = jnp.asarray([[3, 1, 0], [2, 4, 0]], jnp.int32)
    out = gather_pages(pool, table)
    assert out.shape == (2, 12, 6)
    np.testing.assert_array_equal(np.asarray(out[0, :4]), np.asarray(pool[3]))
    np.testing.assert_array_equal(np.asarray(out[1, 4:8]), np.asarray(pool[4]))
