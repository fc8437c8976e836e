"""End-to-end behaviour: training reduces loss; PASA attention inside a real
model matches the safe-precision path; serve loop generates coherently;
checkpoint-restart resumes bit-exactly."""

import dataclasses
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.data import DataPipeline
from repro.launch.steps import TrainHyper, init_train_state, make_train_step
from repro.models.model_zoo import build


def _train(cfg, steps=30, batch=8, seq=32, seed=0):
    bundle = build(cfg)
    hyper = TrainHyper(peak_lr=3e-3, warmup_steps=5, total_steps=steps)
    step = jax.jit(make_train_step(bundle, hyper))
    state = init_train_state(bundle, jax.random.PRNGKey(seed))
    pipe = DataPipeline(batch=batch, seq=seq, vocab=cfg.vocab_size, seed=seed)
    losses = []
    for _ in range(steps):
        b = next(pipe)
        b = {k: jnp.asarray(v) for k, v in b.items()}
        state, m = step(state, b)
        losses.append(float(m["loss"]))
    pipe.close()
    return losses, state


def test_training_reduces_loss():
    cfg = get_config("qwen3-4b").reduced()
    losses, _ = _train(cfg, steps=40)
    first, last = np.mean(losses[:5]), np.mean(losses[-5:])
    assert last < first - 0.5, (first, last)


def test_training_with_pasa_attention_matches_flash():
    """PASA (fully-fp16 attention) trains to the same loss trajectory as the
    safe fp32-stat flash path on a small model - the paper's end-to-end
    equivalence claim, in training form."""
    base = get_config("qwen3-4b").reduced()
    cfg_pasa = dataclasses.replace(
        base, attention=dataclasses.replace(base.attention, impl="pasa")
    )
    cfg_flash = dataclasses.replace(
        base, attention=dataclasses.replace(base.attention, impl="flash",
                                            policy="fp32")
    )
    l_pasa, _ = _train(cfg_pasa, steps=25)
    l_flash, _ = _train(cfg_flash, steps=25)
    # identical data and init; trajectories should track closely
    assert abs(l_pasa[-1] - l_flash[-1]) < 0.35, (l_pasa[-1], l_flash[-1])
    assert np.mean(l_pasa[-5:]) < np.mean(l_pasa[:5])


def test_moe_training_reduces_loss():
    cfg = get_config("olmoe-1b-7b").reduced()
    losses, _ = _train(cfg, steps=30)
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.3


def test_ssm_training_reduces_loss():
    cfg = get_config("falcon-mamba-7b").reduced()
    losses, _ = _train(cfg, steps=30)
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.3


def test_checkpoint_restart_bit_exact():
    """Train 10 steps straight vs 5 + checkpoint + restore + 5: same state."""
    from repro.checkpoint import CheckpointManager

    cfg = get_config("qwen3-4b").reduced()
    bundle = build(cfg)
    hyper = TrainHyper(peak_lr=1e-3, warmup_steps=2, total_steps=10)
    step = jax.jit(make_train_step(bundle, hyper))

    def batches():
        pipe = DataPipeline(batch=4, seq=16, vocab=cfg.vocab_size, seed=1)
        out = [next(pipe) for _ in range(10)]
        pipe.close()
        return [{k: jnp.asarray(v) for k, v in b.items()} for b in out]

    bs = batches()
    s_direct = init_train_state(bundle, jax.random.PRNGKey(7))
    for b in bs:
        s_direct, _ = step(s_direct, b)

    with tempfile.TemporaryDirectory() as d:
        cm = CheckpointManager(d)
        s = init_train_state(bundle, jax.random.PRNGKey(7))
        for b in bs[:5]:
            s, _ = step(s, b)
        cm.save(5, s, blocking=True)
        _, s2 = cm.restore(jax.eval_shape(lambda: s))
        s2 = jax.tree.map(jnp.asarray, s2)
        for b in bs[5:]:
            s2, _ = step(s2, b)

    for a, b_ in zip(jax.tree.leaves(s_direct), jax.tree.leaves(s2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))


def test_serve_generates_self_consistently():
    """Greedy decode twice from the same prompt -> identical continuations."""
    cfg = get_config("qwen3-4b").reduced()
    bundle = build(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    step = jax.jit(
        lambda p, t, pos, c: bundle.serve_step(p, t, pos, c)
    )

    def gen(seed_tok):
        cache = bundle.init_cache(1, 24)
        tok = jnp.asarray([seed_tok], jnp.int32)
        out = []
        for i in range(12):
            logits, cache = step(params, tok, jnp.asarray([i], jnp.int32),
                                 cache)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            out.append(int(tok[0]))
        return out

    assert gen(5) == gen(5)
    assert 0 <= min(gen(5)) and max(gen(5)) < cfg.vocab_size


def test_compile_cache_goes_where_placed(tmp_path):
    """launch/compile_cache: with JAX_COMPILATION_CACHE_DIR set, compiled
    programs land there and the checkout's default directory is not
    configured; without it, the one fixed in-checkout directory is.  Run
    in child interpreters (CPU) so this process's JAX config is untouched."""
    import subprocess
    import sys

    from repro.launch.compile_cache import DEFAULT_DIR

    body = (
        "import jax\n"
        "from repro.launch.compile_cache import enable_compile_cache\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "d = enable_compile_cache()\n"
        "if {compile}:\n"
        "    jax.jit(lambda x: x * 3 + 1)(jax.numpy.ones(5)).block_until_ready()\n"
        "print(d, jax.config.jax_compilation_cache_dir)\n"
    )
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    base = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=src)
    base.pop("JAX_COMPILATION_CACHE_DIR", None)

    outside = tmp_path / "cache"
    out = subprocess.run(
        [sys.executable, "-c", body.format(compile=True)],
        env=dict(base, JAX_COMPILATION_CACHE_DIR=str(outside)),
        capture_output=True, text=True, timeout=300, check=True,
    ).stdout.split()
    assert out == [str(outside), str(outside)]
    assert any(outside.iterdir())

    out = subprocess.run(
        [sys.executable, "-c", body.format(compile=False)],
        env=base, capture_output=True, text=True, timeout=300, check=True,
    ).stdout.split()
    assert out == [str(DEFAULT_DIR), str(DEFAULT_DIR)]
    assert DEFAULT_DIR.name == ".jax_cache"
