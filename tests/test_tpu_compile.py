"""Compiles for a described TPU v5e at qwen3-4b's published widths.

Nothing runs: each test lowers a kernel or a jitted engine step with
shapes placed on one chip of a described ``v5e:2x2`` topology and asks the
TPU compiler for the program.  That catches what interpret mode cannot see
- Mosaic tiling and dtype refusals, VMEM overruns, programs that do not fit
the chip's memory - with no chip attached.  The topology is described
inside a fixture (never at import: only one process may load the TPU
library, and every test worker imports this module).  Keep every such
compile in this file.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.kernels as K
from repro.configs import get_config
from repro.models.model_zoo import build
from repro.runtime import ServeEngine

CFG = dataclasses.replace(get_config("qwen3-4b"), param_dtype="bfloat16")
KVH, G, D = CFG.n_kv_heads, CFG.group, CFG.head_dim
PAGE = CFG.attention.block_kv
B, PROMPT, GEN = 4, 512, 32
N_PAGES = 1 + B * -(-(PROMPT + GEN) // PAGE)
MAX_PAGES = -(-(PROMPT + GEN) // PAGE)
#: v5e HBM per chip (Google Cloud, "TPU v5e")
V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, configured as a chip run is: x64
    off (the suite turns it on for its fp64 oracles; Mosaic index maps
    take no 64-bit integers), and the persistent compilation cache off (a
    program compiled for a described chip can be written to the cache but
    not read back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = {
        name: getattr(jax.config, name)
        for name in ("jax_enable_compilation_cache", "jax_enable_x64")
    }
    for name in prev:
        jax.config.update(name, False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    for name, value in prev.items():
        jax.config.update(name, value)
    cc.reset_cache()


def _spec(sharding):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return s


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _pool(s, kv_dtype):
    """Page pool (P, page, KVH, D) plus sidecars for an int8 pool."""
    pages = [s((N_PAGES, PAGE, KVH, D), kv_dtype)] * 2
    if kv_dtype != jnp.int8:
        return pages, {}
    quant = dict(
        k_scale=s((N_PAGES, KVH), jnp.float32),
        k_shift=s((N_PAGES, KVH, D), jnp.float32),
        v_scale=s((N_PAGES, KVH), jnp.float32),
        v_shift=s((N_PAGES, KVH, D), jnp.float32),
    )
    return pages, quant


def _assert_kernel(compiled):
    """The Mosaic kernel is in the program (compiled, not interpreted)."""
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8])
def test_paged_decode_kernel_compiles(one_chip, kv_dtype):
    s = _spec(one_chip)
    pages, quant = _pool(s, kv_dtype)
    names = tuple(quant)

    def f(q, kp, vp, table, kv_len, *sidecars):
        return K.pasa_paged_decode(
            q, kp, vp, table, kv_len, **dict(zip(names, sidecars))
        )

    _assert_kernel(_compile(
        f, s((B, KVH, G, D), jnp.bfloat16), *pages,
        s((B, MAX_PAGES), jnp.int32), s((B,), jnp.int32), *quant.values(),
    ))


@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8])
def test_paged_prefill_kernel_compiles(one_chip, kv_dtype):
    s = _spec(one_chip)
    pages, quant = _pool(s, kv_dtype)
    names = tuple(quant)

    def f(q, kp, vp, table, start, kv_len, *sidecars):
        return K.pasa_paged_prefill(
            q, kp, vp, table, start, kv_len, **dict(zip(names, sidecars))
        )

    _assert_kernel(_compile(
        f, s((B, KVH * G, PROMPT, D), jnp.bfloat16), *pages,
        s((B, MAX_PAGES), jnp.int32), s((B,), jnp.int32),
        s((B,), jnp.int32), *quant.values(),
    ))


def test_pasa_attention_kernel_compiles(one_chip):
    """Fused prefill: the shift-KV GEMM kernel plus the attention sweep."""
    s = _spec(one_chip)
    kv = s((1, KVH, PROMPT, D), jnp.bfloat16)
    compiled = _compile(
        lambda q, k, v: K.pasa_attention(q, k, v, causal=True),
        s((1, KVH * G, PROMPT, D), jnp.bfloat16), kv, kv,
    )
    assert compiled.as_text().count("tpu_custom_call") >= 2


def test_pasa_decode_kernel_compiles(one_chip):
    s = _spec(one_chip)
    cache = s((B, KVH, PROMPT, D), jnp.bfloat16)
    _assert_kernel(_compile(
        lambda q, k, v, kv_len: K.pasa_decode(q, k, v, kv_len),
        s((B, KVH, G, D), jnp.bfloat16), cache, cache, s((B,), jnp.int32),
    ))


def test_engine_decode_step_compiles_two_layers(one_chip):
    """The served decode step at published widths, depth cut to 2 layers:
    bf16 params, a bf16 page pool, page == block_kv == 128."""
    s = _spec(one_chip)
    bundle = build(dataclasses.replace(CFG, n_layers=2))
    params = jax.tree.map(
        lambda x: s(x.shape, x.dtype),
        jax.eval_shape(bundle.init, jax.random.PRNGKey(0)),
    )
    eng = ServeEngine(
        bundle, params, max_batch=B, num_pages=N_PAGES, page_size=PAGE,
        max_seq_len=PROMPT + GEN,
    )
    pool = jax.tree.map(lambda x: s(x.shape, x.dtype), eng.pool)
    i32 = lambda *shape: s(shape, jnp.int32)
    compiled = eng._step_fn.lower(
        params, i32(B), i32(B), pool, i32(B, eng.max_pages_per_seq)
    ).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert used < V5E_HBM_BYTES
