"""BENCHMARK.json against the benchmark's contract, and the files it names:
every cell, configuration, traffic mix and metric is found by its name, and
a new one is a new file that no existing file has to mention."""

import json
import re
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
from traffic import Traffic, quantile  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_benchmark_json_shape():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    names = [c["name"] for c in SPEC["configs"]]
    names += [w["name"] for w in SPEC["workloads"]]
    names += [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", []):
            assert w in e2e[m["moves"]].get("workloads", [w])
    assert len({w["layer"] for w in SPEC["per_layer"]}) >= 3


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_resolves(cell):
    c = harness.load_cell(cell)
    assert c["chips"] in (1, 4)
    assert c["conf"]["source"].startswith("https://")
    assert (BENCH / "references" / f"{c['conf']['reference']}.py").exists()
    assert c["mix"]["loop"] in ("open", "closed")
    if c["mix"]["loop"] == "open":
        assert c["extra"]["rate_per_s"] > 0
    assert c["extra"]["limits"]["logit_gap_max"] > 0
    assert any(m["name"] == "setup_s" for m in c["end_to_end"])
    assert len(c["end_to_end"]) >= 2 and c["per_layer"]
    for m in c["end_to_end"] + c["per_layer"]:
        assert callable(harness.load_reader(m["name"]))


@pytest.mark.parametrize("config", [c["name"] for c in SPEC["configs"]])
def test_config_files_name_their_cuts(config):
    entry = next(c for c in SPEC["configs"] if c["name"] == config)
    conf = json.loads((ROOT / entry["file"]).read_text())
    assert conf["source"] == entry["source"]
    assert conf["reduced"] == entry["reduced"]
    for key in entry["reduced"]:
        assert key in conf and any(a.startswith(key) for a in conf["assumed"])
    assert any(w["config"] == config for w in SPEC["workloads"])


def test_new_files_are_found_with_no_edit(tmp_path):
    """A new mix, configuration, cell file and metric reader are picked up
    by name; no file that was there is touched."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads(json.dumps(SPEC))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*") if p.is_file()}
    base = next(c for c in spec["configs"])
    conf = json.loads((ROOT / base["file"]).read_text())
    (tmp_path / "bench/configs/newmodel.json").write_text(json.dumps(conf))
    mix = json.loads((BENCH / "traffic/chat.json").read_text())
    mix["prompt_len"]["median"] = 512
    (tmp_path / "bench/traffic/newmix.json").write_text(json.dumps(mix))
    (tmp_path / "bench/cells/newmodel.newmix.json").write_text(
        json.dumps({"rate_per_s": 1.5, "limits": {"logit_gap_max": 1.0}}))
    (tmp_path / "bench/metrics/new_metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    spec["configs"].append(dict(base, name="newmodel", file="bench/configs/newmodel.json"))
    spec["workloads"].append({"name": "newmodel.newmix", "config": "newmodel",
                              "traffic": "newmix", "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "new_metric", "unit": "%", "better": "higher",
                              "source": "program_counter", "layer": "test",
                              "moves": "setup_s", "workloads": ["newmodel.newmix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    c = harness.load_cell("newmodel.newmix", root=tmp_path)
    assert c["mix"]["prompt_len"]["median"] == 512
    assert c["extra"]["rate_per_s"] == 1.5
    assert [m["name"] for m in c["per_layer"]] == ["new_metric"]
    assert harness.load_reader("new_metric", bench=tmp_path / "bench")(None) == 42.0
    for p, data in before.items():
        assert p.read_bytes() == data
    with pytest.raises(harness.BenchError):
        harness.load_cell("no.such.cell", root=tmp_path)


@pytest.mark.parametrize("mix", ["chat", "longdoc"])
def test_traffic_is_a_function_of_the_seed(mix):
    spec = json.loads((BENCH / f"traffic/{mix}.json").read_text())
    rate = 2.0 if spec["loop"] == "open" else None
    seed = 2**31 + 12345                      # beyond 32 signed bits
    secs = 51.0 if rate else None
    a = Traffic(spec, seed, 151936, rate, secs)
    b = Traffic(spec, seed, 151936, rate, secs)
    c = Traffic(spec, seed + 1, 151936, rate, secs)
    n = a.deck
    ra = [a.request(i) for i in range(n)]
    rb = [b.request(i) for i in range(n)]
    rc = [c.request(i) for i in range(n)]
    for (pa, ga, da), (pb, gb, db) in zip(ra, rb):
        assert np.array_equal(pa, pb) and ga == gb and da == db
    # another seed deals the same sizes in another order
    assert sorted(len(p) for p, _, _ in ra) == sorted(len(p) for p, _, _ in rc)
    assert sorted(g for _, g, _ in ra) == sorted(g for _, g, _ in rc)
    assert [len(p) for p, _, _ in ra] != [len(p) for p, _, _ in rc]
    lo, hi = spec["prompt_len"]["min"], spec["prompt_len"]["max"]
    assert all(lo <= len(p) <= hi and p.dtype == np.int32 for p, _, _ in ra)
    if rate:
        dues = [d for _, _, d in ra]
        assert dues == sorted(dues) and dues[0] == 0.0
        # one deck of arrivals fills the window, whatever the seed
        assert n == round(rate * secs)
        assert a.request(n)[2] == pytest.approx(secs)
        assert c.request(n)[2] == pytest.approx(secs)
        assert max(dues) < secs


def test_length_quantiles():
    spec = {"dist": "lognormal", "median": 256, "sigma": 0.8, "min": 32, "max": 1024}
    assert quantile(spec, 0.5) == 256
    assert quantile(spec, 1e-9) == 32 and quantile(spec, 1 - 1e-9) == 1024
    uni = {"dist": "uniform", "min": 64, "max": 256}
    assert quantile(uni, 0.0) == 64 and quantile(uni, 0.999999) == 256
