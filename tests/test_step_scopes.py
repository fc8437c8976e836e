"""The model step's parts are named on the device trace: every op of the
compiled decode and paged-prefill programs carries the ``jax.named_scope``
of its part in its ``op_name`` metadata, which is what a profiler trace
shows for each device op (``bench/scope_reduce.py`` reads it)."""

import re
from contextlib import nullcontext

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.models.model_zoo import build
from repro.models.transformer import STEP_SCOPES
from repro.runtime import ServeEngine


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("qwen3-4b").reduced()
    bundle = build(cfg)
    return bundle, bundle.init(jax.random.PRNGKey(0))


def _compiled_text(bundle, params, program, temperature):
    eng = ServeEngine(
        bundle, params, max_batch=2, num_pages=16, page_size=8,
        max_seq_len=32, prefill_chunk=16, temperature=temperature,
    )
    b, mp = eng.max_batch, eng.max_pages_per_seq
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    sample = (i32(b), i32(b)) if temperature > 0 else ()
    if program == "decode":
        args = (params, i32(b), i32(b), eng.pool, i32(b, mp)) + sample
        fn = eng._step_fn
    else:
        pb, cs = eng.prefill_batch, eng.prefill_chunk
        args = (params, i32(pb, cs), i32(pb), i32(pb), i32(pb), eng.pool,
                i32(pb, mp)) + sample
        fn = eng._prefill_fn
    return fn.lower(*args).compile().as_text()


def _instructions(text):
    """The compiled program's instructions, metadata and the debug-info
    tables (source files, stack frames) left out, and each name replaced
    by its order of first use (a name's numeric suffix may differ)."""
    lines = [ln for ln in text.splitlines() if " = " in ln and "%" in ln]
    names = {}

    def canonical(m):
        return "%" + str(names.setdefault(m.group(1), len(names)))

    return [
        re.sub(r"%([\w.-]+)", canonical,
               re.sub(r",? ?metadata=\{[^}]*\}", "", ln))
        for ln in lines
    ]


@pytest.mark.parametrize("program,temperature", [
    ("decode", 0.0), ("prefill", 0.0), ("decode", 0.8),
], ids=["decode", "prefill", "decode-sampled"])
def test_compiled_step_carries_every_scope(tiny, program, temperature):
    names = re.findall(
        r'op_name="([^"]*)"', _compiled_text(*tiny, program, temperature)
    )
    parts = [set(n.split("/")) for n in names]
    for scope in STEP_SCOPES:
        assert any(scope in p for p in parts), f"no op under {scope!r}"
    # the parts are disjoint (``layer_scan`` holds the layers' parts)
    step_parts = set(STEP_SCOPES) - {"layer_scan"}
    assert all(len(p & step_parts) <= 1 for p in parts)
    # the model's matmuls all sit under a part
    dots = [p for n, p in zip(names, parts) if n.endswith("/dot_general")]
    assert dots and all(p & step_parts for p in dots)


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_scopes_change_only_metadata(tiny, program, monkeypatch):
    """The compiled program with its scopes is the program without them,
    instruction for instruction (fusions, layouts, order): only the
    ``op_name`` metadata differs."""
    scoped = _compiled_text(*tiny, program, 0.0)
    monkeypatch.setattr(jax, "named_scope", lambda name: nullcontext())
    plain = _compiled_text(*tiny, program, 0.0)
    assert "/pasa/" in scoped and "/pasa/" not in plain
    assert len(_instructions(plain)) > 100
    assert _instructions(scoped) == _instructions(plain)
