"""The correctness check of a run, at a size the CPU holds: a sound run
passes; a run whose served path is broken underneath fails, once for each
fault a served cell can have; and the control (the reference computed in
float8, put in the program's place) fails the same limit.

The tiny configuration and mix are in ``bench/testdata``.  The limit, 0.03,
lies between what sound runs read at this size (0.0 to 0.0115 over seeds 1
to 8) and what the control reads (0.074 to 0.257 over the same seeds); the
faults read 0.25 and more.  Which requests finish in the window, and so the
sample, depends on timing; the engine's tokens do not.
"""

import dataclasses
import json
import shutil
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import harness  # noqa: E402

LIMIT = 0.03
SEEDS = (1, 3)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout holding one tiny open-loop cell."""
    root = tmp_path_factory.mktemp("bench_root")
    for d in ("configs", "traffic", "cells"):
        (root / "bench" / d).mkdir(parents=True)
    shutil.copy(BENCH / "testdata/tiny.json", root / "bench/configs/tiny.json")
    shutil.copy(BENCH / "testdata/tiny_traffic.json", root / "bench/traffic/tiny.json")
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny", "source": "test", "reduced": [],
                        "file": "bench/configs/tiny.json", "why": "test"}]
    spec["workloads"] = [{"name": "tiny.chat", "config": "tiny",
                          "traffic": "tiny", "chips": 1, "why": "test"}]
    spec["end_to_end"] = [m for m in spec["end_to_end"] if "workloads" not in m]
    spec["per_layer"] = []
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    (root / "bench/cells/tiny.chat.json").write_text(json.dumps(
        {"rate_per_s": 20.0, "limits": {"logit_gap_max": LIMIT}}))
    return root


def run_cell(root, seed, hook=None):
    return harness.execute("tiny.chat", seed, 1.0, 0, root=root,
                           require_tpu=False, bundle_hook=hook)


def token_altered(bundle):
    """Every decode step's token is not the one its logits pick."""
    step = bundle.paged_serve_step

    def broken(p, t, pos, c, pt):
        logits, pool = step(p, t, pos, c, pt)
        return jnp.roll(logits, 1, axis=-1), pool

    return dataclasses.replace(bundle, paged_serve_step=broken)


def state_unchanged(bundle):
    """The decode step returns the page pool it was given: no K/V written."""
    step = bundle.paged_serve_step

    def broken(p, t, pos, c, pt):
        logits, _ = step(p, t, pos, c, pt)
        return logits, c

    return dataclasses.replace(bundle, paged_serve_step=broken)


@pytest.mark.parametrize("seed", SEEDS)
def test_sound_run_is_correct(root, seed):
    res = run_cell(root, seed)
    gap = res["checks"]["logit_gap_max"]
    assert res["correct"], res["checks"]
    assert gap["limit"] == LIMIT and 0.0 <= gap["value"] <= LIMIT
    assert res["attempted"] > 10 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"itl_p95_ms", "setup_s"}


@pytest.mark.parametrize("fault", [token_altered, state_unchanged],
                         ids=["token_altered", "state_unchanged"])
def test_broken_path_is_not_correct(root, fault):
    res = run_cell(root, SEEDS[0], hook=fault)
    assert not res["correct"]
    assert res["checks"]["logit_gap_max"]["value"] > LIMIT


@pytest.mark.parametrize("seed", SEEDS)
def test_float8_control_is_not_correct(root, seed):
    run = harness.Run("tiny.chat", seed, 1.0, 0, root=root, require_tpu=False)
    run.setup()
    run.window()
    run.free_engine()
    served, control = run.compare(control=True)
    assert served <= LIMIT < control
