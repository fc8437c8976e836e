"""bench/trace_reduce.py against traces whose answers are known."""

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import trace_reduce  # noqa: E402


def test_reduce_synthetic_trace():
    """Five ops, three program runs and three host annotations; every
    number worked out by hand (nanoseconds in, seconds out)."""
    tr = json.loads((BENCH / "testdata/trace_synthetic.json").read_text())
    red = trace_reduce.reduce(tr)
    # window: first annotation start 900 to last end 2600
    assert red["window_s"] == pytest.approx(1700e-9)
    # ops union: [1000,1200] [1250,1400] [1600,1900] [2100,2500]
    assert red["busy_s"] == pytest.approx(1050e-9)
    assert red["programs"]["jit__device_step"] == pytest.approx([800e-9, 2])
    assert red["programs"]["jit__device_prefill"] == pytest.approx([300e-9, 1])
    assert trace_reduce.program(red, "_device_step") == pytest.approx((800e-9, 2))
    assert trace_reduce.program(red, "_device_verify") is None
    assert [n for n, _ in red["device_ops"]] == ["fusion.1", "fusion.2", "convolution.3"]
    assert red["device_ops"][0][1] == pytest.approx(400e-9)
    # gaps, longest first, each named by the annotation overlapping it most
    # (the tie at [1900, 2100] goes to the first annotation found)
    assert [n for n, _ in red["idle_gaps"]] == [
        "bench.step", "bench.wait", "bench.step", "bench.step", "bench.step"]
    assert [g for _, g in red["idle_gaps"]] == pytest.approx(
        [200e-9, 200e-9, 100e-9, 100e-9, 50e-9])


def test_ops_outside_the_window_do_not_count():
    tr = json.loads((BENCH / "testdata/trace_synthetic.json").read_text())
    plane = tr["device"]["/device:TPU:0"]
    plane["XLA Ops"].append(["fusion.9", 3000, 500])
    plane["XLA Modules"].append(["jit__device_step(1)", 3000, 500])
    red = trace_reduce.reduce(tr)
    assert red["busy_s"] == pytest.approx(1050e-9)
    assert red["programs"]["jit__device_step"][1] == 2


def test_load_reads_annotations_of_a_recorded_trace(tmp_path):
    """A trace the profiler writes here: the benchmark's host annotations
    are found; the CPU has no device plane, so there is nothing to reduce."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64), jnp.float32)
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                f(x).block_until_ready()
    tr = trace_reduce.load(str(tmp_path))
    assert [n for n, _, _ in tr["host"]] == ["bench.step"] * 3
    assert all(d > 0 for _, _, d in tr["host"])
    with pytest.raises(ValueError):
        trace_reduce.reduce(tr)


def _busy_by_sweep(events, w0, w1):
    """Covered time inside [w0, w1] by a counting sweep over start/end
    points (a different method from the reduction's interval union)."""
    points = []
    for _, s, d in events:
        s, e = max(s, w0), min(s + d, w1)
        if e > s:
            points += [(s, 1), (e, -1)]
    points.sort()
    busy, depth, last = 0.0, 0, None
    for t, step in points:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


def test_reduce_recorded_decode_step():
    """One host step of the qwen3-4b chat cell as a TPU v5e recorded it
    (the device ops that overlap it, names shortened)."""
    import gzip

    tr = json.loads(gzip.open(BENCH / "testdata/trace_tpu_v5e_decode_step.json.gz").read())
    (plane,) = tr["device"].values()
    (host,) = tr["host"]
    w0, w1 = host[1], host[1] + host[2]
    red = trace_reduce.reduce(tr)
    assert red["window_s"] == pytest.approx(host[2] * 1e-9)
    busy = _busy_by_sweep(plane["XLA Ops"], w0, w1)
    assert red["busy_s"] == pytest.approx(busy * 1e-9)
    assert 0.95 < red["busy_s"] / red["window_s"] <= 1.0
    runs = [e for e in plane["XLA Modules"] if w0 <= e[1] < w1 and "_device_step" in e[0]]
    assert len(runs) == 1
    assert trace_reduce.program(red, "_device_step") == pytest.approx((runs[0][2] * 1e-9, 1))
    # self times of properly nested ops add up to the covered time
    clipped = [[n, max(s, w0), min(s + d, w1) - max(s, w0)]
               for n, s, d in plane["XLA Ops"] if s + d > w0 and s < w1]
    assert sum(d for _, d in trace_reduce._self_times(clipped)) == pytest.approx(busy)
    assert red["device_ops"][0][0] == "select_convert_fusion.4 fusion"
