"""bench/scope_reduce.py against traces whose answers are worked out by
hand, and the readers of the engine's per-step work counters."""

import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import scope_reduce  # noqa: E402


def _synthetic():
    return json.loads((BENCH / "testdata/scope_synthetic.json").read_text())


def test_scope_self_times_per_program():
    """Two decode runs and one prefill run start in the window [900,
    2600); the third decode run (at 3000) does not count.  Each op counts
    its self time toward the innermost scope of its op_name: the while
    loop keeps 400 - 100 - 150 - 100 = 50 ns, unscoped."""
    red = scope_reduce.reduce(_synthetic())
    assert red["window_s"] == pytest.approx(1700e-9)
    step = red["programs"]["jit__device_step"]
    assert step["runs"] == 2
    assert step["s"] == pytest.approx(900e-9)
    want = {"qkv": 300, "kv_gather": 150, "pasa": 100, "head": 50,
            "sample": 20, "kv_write": 150, "unscoped": 100}
    assert step["scopes"] == pytest.approx({k: v * 1e-9 for k, v in want.items()})
    # the parts and the uncovered 30 ns inside the first run make the runs
    assert sum(step["scopes"].values()) + 30e-9 == pytest.approx(step["s"])
    pre = red["programs"]["jit__device_prefill"]
    assert pre["runs"] == 1 and pre["s"] == pytest.approx(300e-9)
    assert pre["scopes"] == pytest.approx({"pasa": 200e-9, "mlp": 100e-9})
    assert scope_reduce.per_run_ms(red, "_device_step", ["kv_gather"]) == \
        pytest.approx(150e-9 / 2 * 1e3)
    assert scope_reduce.per_run_ms(
        red, "_device_step", scope_reduce.SCOPES + ("unscoped",)
    ) == pytest.approx(870e-9 / 2 * 1e3)
    assert scope_reduce.per_run_ms(red, "_device_verify", ["pasa"]) is None


def test_idle_labelled_by_innermost_span():
    """Gaps [900,1000] [1400,1420] [1490,1600] [1900,2000] [2400,2600]:
    a serve.* annotation names the time it covers before the bench.* one
    around it, the latest-starting serve.* one first."""
    red = scope_reduce.reduce(_synthetic())
    got = dict(red["idle_by_span"])
    want = {"bench.step": 50 + 20 + 10 + 200, "serve.dispatch": 50,
            "serve.retire.wait": 60, "bench.wait": 50 + 50, "serve.plan": 40}
    assert got == pytest.approx({k: v * 1e-9 for k, v in want.items()})
    assert sum(got.values()) == pytest.approx(530e-9)
    assert [k for k, _ in red["idle_by_span"]][0] == "bench.step"


def test_scope_of_an_op_name():
    assert scope_reduce.scope_of("jit(f)/while/body/mlp/jit(silu)/logistic") == "mlp"
    assert scope_reduce.scope_of("jit(f)/qkv/x/pasa/dot_general") == "pasa"
    assert scope_reduce.scope_of("jit(f)/while") == "unscoped"
    assert scope_reduce.scope_of("") == "unscoped"
    # a scope's name only counts as a whole component
    assert scope_reduce.scope_of("jit(f)/mlp_head/add") == "unscoped"


HLO = """HloModule jit__device_step, is_scheduled=true

%fused_computation (param_0: bf16[4]) -> bf16[4] {
  ROOT %mul.1 = bf16[4]{0} multiply(%param_0, %param_0), metadata={op_name="jit(f)/mlp/mul"}
}

ENTRY %main (p: bf16[4]) -> bf16[4] {
  %p = bf16[4]{0} parameter(0), metadata={op_name="pool[\'k\']"}
  %fusion.1 = bf16[4]{0} fusion(%p), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/mlp/mul"}
  %bitcast.2 = bf16[2,2]{1,0} bitcast(%fusion.1)
  %copy-start = (bf16[2,2]{1,0}, bf16[2,2]{1,0}, u32[]) copy-start(%bitcast.2)
  %copy-done = bf16[2,2]{1,0} copy-done(%copy-start)
  ROOT %copy.3 = bf16[4]{0} copy(%p)
}
"""


def test_op_names_from_hlo_text():
    """Each instruction's op_name; one without takes its first operand's,
    through bitcasts and async copies, or "" from an unnamed parameter."""
    module, names = scope_reduce.op_names(HLO)
    assert module == "jit__device_step"
    assert names["fusion.1"] == names["mul.1"] == "jit(f)/mlp/mul"
    assert names["copy-done"] == names["bitcast.2"] == "jit(f)/mlp/mul"
    assert names["copy.3"] == "pool['k']"
    assert scope_reduce._instruction(
        "%copy-done = bf16[2,2]{1,0} copy-done(%copy-start)") == "copy-done"
    assert scope_reduce._instruction("while.65 while") == "while.65"
    # ops named through the map when their events carry no op_name
    tr = {"device": {"/device:TPU:0": {
        "XLA Modules": [["jit__device_step(7)", 0, 100]],
        "XLA Ops": [["fusion.1", 0, 60, ""], ["copy-done", 60, 30, ""],
                    ["copy.3", 90, 10, ""]]}},
        "host": [["bench.step", 0, 100]]}
    red = scope_reduce.reduce(tr, dict([(module, names)]))
    assert red["programs"]["jit__device_step"]["scopes"] == pytest.approx(
        {"mlp": 90e-9, "unscoped": 10e-9})


def test_scope_names_are_the_programs():
    """The reducer writes the scope names out; the program keeps them in
    one tuple.  A rename has to be made in both."""
    from repro.models.transformer import STEP_SCOPES

    assert scope_reduce.SCOPES == STEP_SCOPES


def _run(counters, t0=10.0, t1=20.0, offset=5.0):
    from repro.runtime.telemetry import TraceEvent

    evs = [TraceEvent("counter", "engine", i, ts - offset, args=args)
           for i, (ts, args) in enumerate(counters)]
    evs.append(TraceEvent("span", "plan", 0, 12.0 - offset, 0.001))
    return SimpleNamespace(tel_events=evs, t0=t0, t1=t1, tel_offset=offset)


def test_work_readers():
    """Sums over the counter samples of the steps that end inside the
    window; the sample at 9.0 lies before it and the one at 20.0 after."""
    work = lambda live, slots, tok, grid: dict(  # noqa: E731
        waiting=0, running=1, free_pages=3, inflight=1, decode_rows=2,
        kv_live=live, kv_slots=slots, prefill_tokens=tok, prefill_slots=grid)
    run = _run([(9.0, work(1, 1, 1, 1)), (10.0, work(30, 64, 21, 32)),
                (15.0, work(34, 64, 0, 0)), (20.0, work(64, 64, 32, 32))])
    kv = harness.load_reader("kv_gather_useful")
    grid = harness.load_reader("prefill_grid_useful")
    assert kv(run) == pytest.approx(64 / 128 * 100)
    assert grid(run) == pytest.approx(21 / 32 * 100)
    # no prefill call in the window: nothing to share
    assert grid(_run([(15.0, work(34, 64, 0, 0))])) is None
    # an engine that counts no work (older programs), or no telemetry
    old = _run([(15.0, dict(waiting=0, running=1, free_pages=3, inflight=1))])
    assert kv(old) is None and grid(old) is None
    assert kv(SimpleNamespace(tel_events=[])) is None


def test_scopes_cover_a_recorded_decode_step():
    """One run of the qwen3-4b chat cell's decode program as a TPU v5e
    recorded it (its 9129 ops by instruction name, the host annotations
    around it) with the op names of the compiled program's instructions:
    every op resolves to an op_name, and the named scopes hold all of the
    run's device time but microseconds."""
    import gzip

    fx = json.loads(gzip.open(BENCH / "testdata/scope_tpu_v5e_decode_step.json.gz").read())
    (lines,) = fx["device"].values()
    (names,) = fx["names"].values()
    assert all(op[0] in names for op in lines["XLA Ops"])
    red = scope_reduce.reduce(fx, fx["names"])
    step = red["programs"]["jit__device_step"]
    assert step["runs"] == 1
    (run,) = [m for m in lines["XLA Modules"] if "_device_step" in m[0]]
    assert step["s"] == pytest.approx(run[2] * 1e-9)
    scopes = step["scopes"]
    assert sum(scopes.values()) <= step["s"]
    assert sum(scopes.values()) > 0.999 * step["s"]
    assert scopes["unscoped"] < 1e-4 * step["s"]
    for part in ("embed", "qkv", "kv_write", "kv_gather", "pasa", "attn_out",
                 "mlp", "head", "layer_scan"):
        assert scopes[part] > 0, part
    # the decode parts the benchmark reads, with what no part names, sum
    # to the run's time
    parts = [["kv_gather"], ["kv_write"], ["pasa"],
             ["embed", "qkv", "attn_out", "mlp", "head", "sample"]]
    ms = [scope_reduce.per_run_ms(red, "_device_step", p) for p in parts]
    rest = run[2] * 1e-6 - sum(ms)
    assert rest == pytest.approx(
        (scopes["layer_scan"] + scopes["unscoped"]) * 1e3, abs=1e-3)
    # the engine's phases are on the host plane around it
    assert {n for n, _, _ in fx["host"]} >= {
        "bench.step", "serve.plan", "serve.dispatch", "serve.decode",
        "serve.retire", "serve.retire.wait"}
