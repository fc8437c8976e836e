"""Unit tests for the bit-safety invariant analyzer (PR 10).

Each of the five rules is exercised on inline good/bad fixture snippets
(a seeded violation MUST fail, the repo's sanctioned idioms MUST pass),
plus suppression-comment and baseline-file semantics, the rule
registry, and the JSON reporter schema - pinned by a regression test
because tools/ci.sh consumes it.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.analysis import (
    Finding,
    SourceFile,
    all_rules,
    analyze,
    get_rule,
    load_baseline,
    repo_root,
    write_baseline,
)
from repro.analysis.baseline import BASELINE_SCHEMA, split_baselined
from repro.analysis.report import JSON_SCHEMA

ROOT = repo_root()

EXPECTED_RULE_IDS = {
    "readback-outside-drain",
    "dtype-less-random",
    "narrow-accumulation",
    "device-side-tenant-leak",
    "hidden-nondeterminism",
}


def _check(rule_id, path, source):
    """Run one rule over an inline snippet; return active findings."""
    sf = SourceFile.from_source(path, textwrap.dedent(source))
    rule = get_rule(rule_id)
    return [
        f
        for f in rule.check(sf)
        if not sf.is_suppressed(f.rule, f.line)
    ]


# ---------------------------------------------------------------- registry --


def test_registry_has_the_five_invariant_rules():
    ids = {r.id for r in all_rules()}
    assert EXPECTED_RULE_IDS <= ids
    assert len(ids) >= 5
    for r in all_rules():
        assert r.title and r.scope and r.motivation, r.id


def test_unknown_rule_id_fails_loudly():
    with pytest.raises(KeyError):
        get_rule("no-such-rule")


def test_rule_scoping():
    assert get_rule("narrow-accumulation").applies("src/repro/kernels/x.py")
    assert get_rule("narrow-accumulation").applies("src/repro/core/pasa.py")
    assert not get_rule("narrow-accumulation").applies(
        "src/repro/models/attention.py"
    )
    assert get_rule("hidden-nondeterminism").applies(
        "src/repro/runtime/scheduler.py"
    )
    assert not get_rule("hidden-nondeterminism").applies(
        "src/repro/runtime/telemetry.py"
    )
    assert get_rule("dtype-less-random").applies("tests/test_paged.py")
    assert get_rule("dtype-less-random").applies("benchmarks/common.py")


# ------------------------------------------------- readback-outside-drain --

ENGINE_PATH = "src/repro/runtime/engine.py"


def test_readback_rule_flags_each_forbidden_form():
    src = """\
        import numpy as np
        import jax

        class ServeEngine:
            def a(self, x):
                return np.asarray(x)
            def b(self, x):
                return jax.device_get(x)
            def c(self, x):
                x.block_until_ready()
            def d(self, x):
                return x.item()
    """
    findings = _check("readback-outside-drain", ENGINE_PATH, src)
    assert len(findings) == 4


def test_readback_rule_allows_drain_marked_and_host_copies():
    src = """\
        import numpy as np

        class ServeEngine:
            @_drain_point
            def _retire_one(self, x):
                return np.asarray(x)
            def _dispatch(self, table):
                return np.array(table)     # host copy convention: legal
            def _tolist(self, d):
                return list(d.items())     # dict.items != .item()
    """
    assert _check("readback-outside-drain", ENGINE_PATH, src) == []


# ------------------------------------------------------- dtype-less-random --

TEST_PATH = "tests/test_fixture.py"


def test_random_rule_flags_dtypeless_draws():
    src = """\
        import jax

        def make(key, shape):
            a = jax.random.normal(key, shape)
            b = jax.random.uniform(key, shape, minval=0.0, maxval=1.0)
            c = jax.random.truncated_normal(key, -2.0, 2.0, shape)
            return a, b, c
    """
    findings = _check("dtype-less-random", TEST_PATH, src)
    assert len(findings) == 3
    assert {f.line for f in findings} == {4, 5, 6}


def test_random_rule_accepts_explicit_dtypes():
    src = """\
        import jax
        import jax.numpy as jnp

        def make(key, shape):
            a = jax.random.normal(key, shape, jnp.float32)        # positional
            b = jax.random.uniform(key, shape, dtype=jnp.float32,
                                   minval=0.0, maxval=1.0)
            c = jax.random.truncated_normal(
                key, -2.0, 2.0, shape, jnp.bfloat16)              # pos idx 4
            d = jax.random.split(key, 3)                          # not a draw
            return a, b, c, d
    """
    assert _check("dtype-less-random", TEST_PATH, src) == []


def test_random_rule_sees_through_import_aliases():
    src = """\
        import jax.random as jr
        from jax import random
        from jax.random import normal as draw

        def make(key, shape):
            return jr.normal(key, shape), random.uniform(key, shape), \\
                draw(key, shape)
    """
    findings = _check("dtype-less-random", TEST_PATH, src)
    assert len(findings) == 3


def test_random_rule_ignores_numpy_random():
    src = """\
        import numpy as np

        def make(shape):
            return np.random.normal(size=shape)   # out of scope for THIS rule
    """
    assert _check("dtype-less-random", TEST_PATH, src) == []


# ----------------------------------------------------- narrow-accumulation --

KERNEL_PATH = "src/repro/kernels/fixture_kernel.py"


def test_accum_rule_flags_implicit_and_narrow_reductions():
    src = """\
        import jax.numpy as jnp

        def block_update(s, p):
            l_loc = jnp.sum(p, axis=-1)                    # implicit dtype
            m_loc = jnp.max(s, axis=-1)                    # implicit dtype
            r = jnp.cumsum(p, axis=-1)                     # implicit dtype
            bad = jnp.sum(p.astype(jnp.float16), axis=-1)  # narrow cast
            worse = jnp.sum(p, dtype=jnp.float16)          # narrow kwarg
            return l_loc, m_loc, r, bad, worse
    """
    findings = _check("narrow-accumulation", KERNEL_PATH, src)
    assert len(findings) == 5


def test_accum_rule_accepts_the_wide_accumulation_convention():
    src = """\
        import jax
        import jax.numpy as jnp

        def block_update(s, p, valid, wide, stat_dtype):
            count = jnp.sum(valid.astype(wide))
            sbar = jnp.sum(jnp.where(valid, s.astype(wide), 0.0), axis=-1)
            m_loc = jnp.max(s.astype(stat_dtype), axis=-1)
            l_wid = jnp.sum(p, dtype=jnp.float32, axis=-1)
            l_pet = jnp.sum(p, preferred_element_type=jnp.float32)
            ones = jax.lax.dot_general(
                p, p, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            l_dg = jnp.sum(ones.astype(wide))
            return count, sbar, m_loc, l_wid, l_pet, l_dg
    """
    assert _check("narrow-accumulation", KERNEL_PATH, src) == []


def test_accum_rule_out_of_scope_files_untouched():
    rule = get_rule("narrow-accumulation")
    assert not rule.applies("tests/test_kernels.py")
    assert not rule.applies("src/repro/runtime/engine.py")


# ------------------------------------------------- device-side-tenant-leak --


def test_tenant_rule_flags_labels_in_jitted_functions():
    src = """\
        import jax
        import jax.numpy as jnp

        def _device_step(params, token, tenant_ids, pool):
            return jnp.take(pool, tenant_ids)

        step_fn = jax.jit(_device_step)
        pick = jax.jit(lambda priority, x: x[priority])
    """
    findings = _check("device-side-tenant-leak", ENGINE_PATH, src)
    assert len(findings) >= 2
    blob = " ".join(f.message for f in findings)
    assert "tenant_ids" in blob and "priority" in blob


def test_tenant_rule_traces_shard_map_wrapping():
    src = """\
        import jax
        _shard_map = jax.shard_map

        def _device_step(params, token, pool, req_id_vec):
            return pool[req_id_vec]

        wrapped = _shard_map(wrap(_device_step, 3), mesh=None,
                             in_specs=(), out_specs=())
        fn = jax.jit(wrapped)
    """
    findings = _check("device-side-tenant-leak", ENGINE_PATH, src)
    assert len(findings) >= 1
    assert "req_id_vec" in findings[0].message


def test_tenant_rule_allows_host_side_label_use():
    src = """\
        import jax
        import jax.numpy as jnp

        class ServeEngine:
            def submit(self, prompt, tenant=None, priority="throughput"):
                self._tenants[tenant] = priority    # host-only: fine

        def _device_step(params, token, pool):
            return jnp.argmax(token), pool

        step_fn = jax.jit(_device_step)
    """
    assert _check("device-side-tenant-leak", ENGINE_PATH, src) == []


def test_tenant_rule_flags_string_keys_on_device():
    src = """\
        import jax

        def _device_step(params, aux):
            return aux["tenant"]

        fn = jax.jit(_device_step)
    """
    findings = _check("device-side-tenant-leak", ENGINE_PATH, src)
    assert len(findings) == 1


# ------------------------------------------------- hidden-nondeterminism --

SCHED_PATH = "src/repro/runtime/scheduler.py"


def test_determ_rule_flags_wall_clock_and_stdlib_random():
    src = """\
        import random
        import time

        def admission_order(waiting):
            t = time.time()
            random.shuffle(waiting)
            return waiting
    """
    findings = _check("hidden-nondeterminism", SCHED_PATH, src)
    assert len(findings) == 2


def test_determ_rule_flags_set_iteration():
    src = """\
        def plan(waiting, running):
            victims = []
            for r in set(running):               # hash-ordered: flagged
                victims.append(r)
            ids = [v for v in {w.req_id for w in waiting}]   # comp over set
            return victims, ids
    """
    findings = _check("hidden-nondeterminism", SCHED_PATH, src)
    assert len(findings) == 2


def test_determ_rule_accepts_sorted_sets_and_jax_random():
    src = """\
        from jax import random

        def plan(waiting, seen):
            for r in sorted(set(waiting)):       # sorted: deterministic
                pass
            keys = random.split(random.PRNGKey(0), 2)   # jax.random: fine
            present = 3 in {1, 2, 3}             # membership: order-free
            return keys, present
    """
    assert _check("hidden-nondeterminism", SCHED_PATH, src) == []


def test_determ_rule_scoped_to_scheduler_only():
    # telemetry's wall-clock tracing is observability, not a plan input
    assert not get_rule("hidden-nondeterminism").applies(
        "src/repro/runtime/telemetry.py"
    )


# ------------------------------------------------------------ suppressions --


def test_suppression_same_line_and_standalone_line():
    src = """\
        import jax

        def make(key, shape):
            a = jax.random.normal(key, shape)  # repro: allow[dtype-less-random] fixture wants ambient dtype
            # repro: allow[dtype-less-random] second form: annotation line above
            b = jax.random.normal(key, shape)
            c = jax.random.normal(key, shape)  # repro: allow[readback-outside-drain] wrong id
            d = jax.random.normal(key, shape)
            return a, b, c, d
    """
    sf = SourceFile.from_source(TEST_PATH, textwrap.dedent(src))
    rule = get_rule("dtype-less-random")
    raw = rule.check(sf)
    assert len(raw) == 4
    active = [f for f in raw if not sf.is_suppressed(f.rule, f.line)]
    assert {f.line for f in active} == {7, 8}  # wrong-id + unannotated


def test_suppression_comma_separated_ids():
    src = """\
        import jax

        def make(key, shape):
            # repro: allow[dtype-less-random, readback-outside-drain] both
            return jax.random.normal(key, shape)
    """
    sf = SourceFile.from_source(TEST_PATH, textwrap.dedent(src))
    assert sf.is_suppressed("dtype-less-random", 5)
    assert sf.is_suppressed("readback-outside-drain", 5)
    assert not sf.is_suppressed("narrow-accumulation", 5)


# ---------------------------------------------------------------- baseline --


def test_baseline_roundtrip_and_partition(tmp_path):
    f1 = Finding("tests/a.py", 10, "dtype-less-random", "m1")
    f2 = Finding("tests/b.py", 20, "narrow-accumulation", "m2")
    path = str(tmp_path / "baseline.json")
    write_baseline(path, [f1])
    keys = load_baseline(path)
    assert keys == {f1.key()}
    new, old = split_baselined([f1, f2], keys)
    assert new == [f2] and old == [f1]


def test_baseline_missing_file_is_empty():
    assert load_baseline("/nonexistent/baseline.json") == set()


def test_baseline_schema_mismatch_fails(tmp_path):
    path = tmp_path / "baseline.json"
    path.write_text(json.dumps({"schema": 999, "findings": []}))
    with pytest.raises(ValueError):
        load_baseline(str(path))


def test_committed_baseline_is_empty():
    """Satellite: the checked-in baseline proves the repo is violation-
    free at merge - nothing is grandfathered."""
    keys = load_baseline(os.path.join(ROOT, "tools", "analysis_baseline.json"))
    assert keys == set()


# ------------------------------------------------------------- repo gate --


def _cli(*args, cwd=None, env_extra=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src") + os.pathsep + env.get(
        "PYTHONPATH", ""
    )
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *args],
        capture_output=True, text=True, cwd=cwd or ROOT, env=env,
    )


def test_repo_is_clean_under_all_rules():
    """The acceptance criterion: the analyzer exits 0 on the repo with
    the (empty) committed baseline."""
    proc = _cli("--json")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["findings"] == []
    assert payload["baselined"] == 0


def test_json_reporter_schema_pinned():
    """tools/ci.sh and any dashboarding consume this schema: key
    removals/renames must bump JSON_SCHEMA."""
    proc = _cli("--json")
    payload = json.loads(proc.stdout)
    assert payload["schema"] == JSON_SCHEMA == 1
    assert sorted(payload.keys()) == [
        "baselined", "counts", "exit_code", "files_scanned", "findings",
        "root", "rules", "schema", "suppressed",
    ]
    assert payload["files_scanned"] > 50
    rule_ids = {r["id"] for r in payload["rules"]}
    assert EXPECTED_RULE_IDS <= rule_ids
    for r in payload["rules"]:
        assert sorted(r.keys()) == ["id", "scope", "title"]
    assert Finding("a.py", 1, "x", "m").to_dict() == {
        "path": "a.py", "line": 1, "rule": "x", "message": "m",
    }


def test_cli_end_to_end_with_seeded_violation(tmp_path):
    """A seeded violation fails the gate (exit 1), --baseline-update
    grandfathers it (exit 0, baselined=1), and fixing it leaves a clean
    tree even with the stale baseline entry."""
    (tmp_path / "tests").mkdir()
    (tmp_path / "tools").mkdir()
    bad = tmp_path / "tests" / "test_seeded.py"
    bad.write_text(
        "import jax\n\ndef draw(key):\n"
        "    return jax.random.normal(key, (4,))\n"
    )
    proc = _cli("--root", str(tmp_path))
    assert proc.returncode == 1, proc.stdout
    assert "dtype-less-random" in proc.stdout

    proc = _cli("--root", str(tmp_path), "--baseline-update")
    assert proc.returncode == 0, proc.stdout
    baseline = tmp_path / "tools" / "analysis_baseline.json"
    data = json.loads(baseline.read_text())
    assert data["schema"] == BASELINE_SCHEMA
    assert len(data["findings"]) == 1

    proc = _cli("--root", str(tmp_path), "--json")
    assert proc.returncode == 0
    payload = json.loads(proc.stdout)
    assert payload["baselined"] == 1 and payload["findings"] == []

    bad.write_text(
        "import jax\nimport jax.numpy as jnp\n\ndef draw(key):\n"
        "    return jax.random.normal(key, (4,), jnp.float32)\n"
    )
    proc = _cli("--root", str(tmp_path), "--json")
    assert proc.returncode == 0
    payload = json.loads(proc.stdout)
    assert payload["findings"] == [] and payload["baselined"] == 0


def test_cli_rejects_unknown_suppression_id(tmp_path):
    (tmp_path / "tests").mkdir()
    (tmp_path / "tests" / "test_typo.py").write_text(
        "import jax\n\ndef draw(key):\n"
        "    # repro: allow[dtype-less-randm] typo'd id\n"
        "    return jax.random.normal(key, (4,))\n"
    )
    proc = _cli("--root", str(tmp_path))
    assert proc.returncode == 2
    assert "dtype-less-randm" in proc.stderr


def test_cli_syntax_error_fails_gate(tmp_path):
    (tmp_path / "tests").mkdir()
    (tmp_path / "tests" / "test_broken.py").write_text("def broken(:\n")
    proc = _cli("--root", str(tmp_path))
    assert proc.returncode == 1
    assert "syntax-error" in proc.stdout


def test_rule_filter_flag(tmp_path):
    (tmp_path / "tests").mkdir()
    (tmp_path / "tests" / "test_seeded.py").write_text(
        "import jax\n\ndef draw(key):\n"
        "    return jax.random.normal(key, (4,))\n"
    )
    proc = _cli("--root", str(tmp_path), "--rule", "narrow-accumulation")
    assert proc.returncode == 0  # seeded violation is out of this rule's scope
    proc = _cli("--root", str(tmp_path), "--rule", "dtype-less-random")
    assert proc.returncode == 1
    proc = _cli("--root", str(tmp_path), "--rule", "no-such-rule")
    assert proc.returncode == 2


def test_tools_lint_wrapper():
    """tools/lint.py bootstraps sys.path itself - no PYTHONPATH needed."""
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "lint.py"),
         "--list-rules"],
        capture_output=True, text=True, env=env,
    )
    assert proc.returncode == 0, proc.stderr
    for rid in EXPECTED_RULE_IDS:
        assert rid in proc.stdout
