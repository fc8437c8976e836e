"""Pallas kernel validation: interpret-mode allclose vs the pure-jnp oracles,
swept over shapes / dtypes / block sizes / causality (per the brief)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import adversarial_inputs as adv
import repro.kernels as K
from adversarial_inputs import adversarial_case  # noqa: F401
from repro.core import F64, FP16, FP16_FP32, FP32, naive_attention, shifting
from repro.core.numerics import rmse
from repro.kernels import ref

I = dict(interpret=True)


def _mk(key, b, h, kvh, s, d, mean=0.0):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, h, s, d), jnp.float32) + mean
    k = jax.random.normal(ks[1], (b, kvh, s, d), jnp.float32) + mean
    v = jax.random.normal(ks[2], (b, kvh, s, d), jnp.float32)
    return q, k, v


SWEEP = [
    # (B, H, KVH, S, D, block_q, block_kv)
    (1, 2, 2, 128, 64, 64, 64),
    (2, 8, 4, 256, 64, 128, 128),
    (1, 4, 1, 256, 128, 128, 64),   # MQA-style
    (1, 5, 5, 384, 32, 128, 128),   # odd heads, ragged-ish
]


@pytest.mark.parametrize("b,h,kvh,s,d,bq,bkv", SWEEP)
def test_pasa_kernel_matches_ref(b, h, kvh, s, d, bq, bkv, rng):
    q, k, v = _mk(rng, b, h, kvh, s, d, mean=2.0)
    got = K.pasa_attention(
        q, k, v, beta=0.984497, policy=FP16, block_q=bq, block_kv=bkv, **I
    )
    want = ref.attention_ref(q, k, v, beta=0.984497, policy=FP16, block_kv=bkv)
    # fp16 tail: tiny absolute tolerance absorbs op-order rounding on
    # near-zero outputs (relative error there is meaningless)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=8e-3, rtol=2e-2,
    )


@pytest.mark.parametrize("b,h,kvh,s,d,bq,bkv", SWEEP[:2])
def test_pasa_kernel_causal(b, h, kvh, s, d, bq, bkv, rng):
    q, k, v = _mk(rng, b, h, kvh, s, d, mean=1.0)
    got = K.pasa_attention(
        q, k, v, beta=0.984497, policy=FP16, block_q=bq, block_kv=bkv,
        causal=True, **I
    )
    want = ref.attention_ref(
        q, k, v, beta=0.984497, policy=FP16, block_kv=bkv, causal=True
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=2e-3, rtol=2e-2,
    )


@pytest.mark.parametrize("policy", [FP16, FP16_FP32, FP32])
def test_flash_kernel_policies(policy, rng):
    q, k, v = _mk(rng, 1, 4, 2, 256, 64)
    got = K.flash_attention(q, k, v, policy=policy, **I)
    want = ref.attention_ref(q, k, v, beta=0.0, policy=policy, block_kv=128)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=2e-3, rtol=2e-2,
    )


def test_kernel_against_fp64_gold(rng):
    """End-to-end: kernel output within fp16 tolerance of exact attention."""
    q, k, v = _mk(rng, 1, 4, 4, 256, 64, mean=3.0)
    gold = naive_attention(
        q.astype(jnp.float64), k.astype(jnp.float64), v.astype(jnp.float64),
        dtype=jnp.float64,
    )
    got = K.pasa_attention(q, k, v, beta=0.984497, policy=FP16, **I)
    assert rmse(got, gold[:, :, ...]) < 0.02


def test_kernel_overflow_robustness(rng):
    """The paper's headline: fully-fp16 kernel survives x0=30 inputs where
    the fp16 flash baseline NaNs."""
    ks = jax.random.split(rng, 3)
    shape = (1, 2, 256, 128)
    mk = lambda k: jax.random.uniform(k, shape, jnp.float32, minval=29.5, maxval=30.5)
    q, k, v = mk(ks[0]), mk(ks[1]), mk(ks[2])
    bad = K.flash_attention(q, k, v, policy=FP16_FP32, **I)
    good = K.pasa_attention(q, k, v, beta=0.984497, policy=FP16, **I)
    assert bool(jnp.isnan(bad).any())
    assert bool(jnp.isfinite(good.astype(jnp.float32)).all())


def test_shift_kv_kernel(rng):
    k = jax.random.normal(rng, (2, 4, 512, 64), jnp.float32) + 5.0
    got = K.shift_kv(k, beta=0.984497, block_kv=128, policy=FP16, **I)
    m = shifting.shifting_matrix(128, 64, 0.984497, jnp.float16)
    want = ref.shift_kv_ref(m, k.astype(jnp.float16), 128)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=1e-2
    )


@pytest.mark.parametrize("kv_lens", [[128, 512], [300, 77], [512, 512]])
@pytest.mark.parametrize("beta", [0.0, 0.9375])
def test_decode_kernel(kv_lens, beta, rng):
    b, kvh, g, d, s2 = 2, 2, 4, 64, 512
    ks = jax.random.split(rng, 3)
    kv_len = jnp.asarray(kv_lens, jnp.int32)
    mask = (jnp.arange(s2) < kv_len[:, None])[:, None, :, None]
    q = jax.random.normal(ks[0], (b, kvh, g, d), jnp.float32) + 1.0
    kc = jnp.where(mask, jax.random.normal(ks[1], (b, kvh, s2, d), jnp.float32) + 2.0, 0.0)
    vc = jnp.where(mask, jax.random.normal(ks[2], (b, kvh, s2, d), jnp.float32), 0.0)
    got = K.pasa_decode(
        q, kc, vc, kv_len, beta=beta, policy=FP16, block_kv=128, **I
    )
    want = ref.decode_ref(
        q.astype(jnp.float16), kc.astype(jnp.float16), vc.astype(jnp.float16),
        kv_len, beta=beta, policy=FP16, block_kv=128,
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=3e-3, rtol=3e-2,
    )
    # against exact attention over the valid prefix
    for bi in range(b):
        L = int(kv_len[bi])
        gold = naive_attention(
            q[bi : bi + 1].astype(jnp.float64),
            kc[bi : bi + 1, :, :L].astype(jnp.float64),
            vc[bi : bi + 1, :, :L].astype(jnp.float64),
            dtype=jnp.float64,
        )
        assert rmse(got[bi : bi + 1], gold) < 0.03


def test_kernel_shape_guards():
    q = jnp.zeros((1, 4, 100, 64), jnp.float16)  # 100 % 128 != 0
    k = jnp.zeros((1, 2, 128, 64), jnp.float16)
    with pytest.raises(ValueError):
        K.pasa_attention(q, k, k, **I)
    with pytest.raises(ValueError):
        K.pasa_attention(jnp.zeros((1, 3, 128, 64), jnp.float16), k, k, **I)


def test_pasa_kernel_on_adversarial_inputs(adversarial_case, rng):
    """The paper's failure generators against the fused prefill kernel:
    the kernel must agree with the pure-jnp oracle at fp32 statistics (the
    'Is Flash Attention Stable?' concern - implementation divergence shows
    up ONLY under stress inputs) and stay finite at the all-fp16 policy
    the paper serves with."""
    b, h, kvh, s, d = 1, 4, 2, 256, 64
    q, k, v = adv.make_adversarial(
        adversarial_case, rng, q_shape=(b, h, s, d), kv_shape=(b, kvh, s, d),
    )
    got = K.pasa_attention(q, k, v, beta=0.984497, policy=FP32, **I)
    want = ref.attention_ref(q, k, v, beta=0.984497, policy=FP32, block_kv=128)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=8e-3, rtol=2e-2,
    )
    got16 = K.pasa_attention(q, k, v, beta=0.984497, policy=FP16, **I)
    assert bool(jnp.isfinite(got16.astype(jnp.float32)).all())


def test_round_f16_matches_ieee_cast():
    """kernels/fp16.round_f16 (the fp16 store of compiled kernels) equals
    ``astype(float16)`` bit for bit: every finite fp16 value, its f32
    neighbours, every tie between adjacent fp16 values, subnormals,
    overflow to inf, and non-finite inputs."""
    from repro.kernels.fp16 import round_f16

    f16 = np.arange(2**16, dtype=np.uint16).view(np.float16)
    f16 = f16[np.isfinite(f16)].astype(np.float32)
    f16.sort()
    ties = ((f16[:-1].astype(np.float64) + f16[1:]) / 2).astype(np.float32)
    x = np.concatenate([
        f16, np.nextafter(f16, np.float32(np.inf)),
        np.nextafter(f16, np.float32(-np.inf)), ties,
        np.random.default_rng(0).standard_normal(10_000).astype(np.float32)
        * np.float32(1e5),
        np.array([65519.99, 65520.0, 1e30, np.inf, -np.inf, np.nan,
                  2.0**-25, 3 * 2.0**-26, -0.0], np.float32),
    ])
    with np.errstate(over="ignore"):
        want = x.astype(np.float16).astype(np.float32)
    got = np.asarray(jax.jit(round_f16)(jnp.asarray(x)))
    same = (got.view(np.uint32) == want.view(np.uint32)) | (
        np.isnan(got) & np.isnan(want)
    )
    assert same.all(), x[~same][:8]


@pytest.fixture
def emulated_kernels(monkeypatch):
    """Run the compiled-path bodies (fp16 held in f32 containers and rounded
    at the policy's storage points, kernels/fp16.py) in interpret mode.
    The jitted kernel calls are swapped for their unjitted bodies so that a
    native trace cached by an earlier test cannot stand in."""
    import importlib

    from repro.kernels import fp16

    emulated = lambda emulate_f16: fp16.KernelNumerics(emulate_f16=True)
    for name, call in (
        ("pasa_attention", "attention_kernel_call"),
        ("pasa_decode", "decode_kernel_call"),
        ("shift_kv", "shift_kv_kernel_call"),
    ):
        # the package re-exports functions under these module names
        mod = importlib.import_module(f"repro.kernels.{name}")
        monkeypatch.setattr(mod, "KernelNumerics", emulated)
        monkeypatch.setattr(mod, call, getattr(mod, call).__wrapped__)


@pytest.mark.parametrize(
    "case", ["attention", "attention_causal", "decode", "shift_kv", "overflow"]
)
def test_emulated_fp16_kernels_vs_reference(case, emulated_kernels, rng):
    """The emulated bodies of pasa_attention, pasa_decode and shift_kv (the
    only bodies these kernels run on a TPU) agree with the pure-jnp oracles
    at the tolerances of the native-kernel tests above, and an fp16 score
    store still overflows where the native fp16 kernel's does."""
    beta = 0.984497
    if case in ("attention", "attention_causal"):
        causal = case == "attention_causal"
        q, k, v = _mk(rng, 2, 8, 4, 256, 64, mean=1.0 if causal else 2.0)
        got = K.pasa_attention(
            q, k, v, beta=beta, policy=FP16, block_q=128, block_kv=128,
            causal=causal, **I
        )
        want = ref.attention_ref(
            q, k, v, beta=beta, policy=FP16, block_kv=128, causal=causal
        )
        tol = dict(atol=2e-3 if causal else 8e-3, rtol=2e-2)
    elif case == "decode":
        b, kvh, g, d, s2 = 2, 2, 4, 64, 512
        ks = jax.random.split(rng, 3)
        kv_len = jnp.asarray([300, 77], jnp.int32)
        mask = (jnp.arange(s2) < kv_len[:, None])[:, None, :, None]
        q = jax.random.normal(ks[0], (b, kvh, g, d), jnp.float32) + 1.0
        kc = jnp.where(
            mask, jax.random.normal(ks[1], (b, kvh, s2, d), jnp.float32) + 2.0, 0.0
        )
        vc = jnp.where(mask, jax.random.normal(ks[2], (b, kvh, s2, d), jnp.float32), 0.0)
        got = K.pasa_decode(
            q, kc, vc, kv_len, beta=0.9375, policy=FP16, block_kv=128, **I
        )
        want = ref.decode_ref(
            q.astype(jnp.float16), kc.astype(jnp.float16),
            vc.astype(jnp.float16), kv_len, beta=0.9375, policy=FP16,
            block_kv=128,
        )
        tol = dict(atol=3e-3, rtol=3e-2)
    elif case == "shift_kv":
        k = jax.random.normal(rng, (2, 4, 512, 64), jnp.float32) + 5.0
        got = K.shift_kv(k, beta=beta, block_kv=128, policy=FP16, **I)
        m = shifting.shifting_matrix(128, 64, beta, jnp.float16)
        want = ref.shift_kv_ref(m, k.astype(jnp.float16), 128)
        tol = dict(atol=1e-2)
    else:
        ks = jax.random.split(rng, 3)
        mk = lambda k: jax.random.uniform(
            k, (1, 2, 256, 128), jnp.float32, minval=29.5, maxval=30.5
        )
        q, k, v = mk(ks[0]), mk(ks[1]), mk(ks[2])
        bad = K.flash_attention(q, k, v, policy=FP16_FP32, **I)
        good = K.pasa_attention(q, k, v, beta=beta, policy=FP16, **I)
        assert bool(jnp.isnan(bad).any())
        assert bool(jnp.isfinite(good.astype(jnp.float32)).all())
        return
    assert got.dtype == want.dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **tol
    )
