"""``BENCHMARK.json`` against the parts of the driver's contract that can be
checked without a chip, and against the files it names: a later PR that
adds an entry finds out here, not in a refused check."""

import json
import os
import re

import pytest

from benchmarks.harness import byname, checks, core

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
# what every reference has (benchmarks/README.md, "The reference's interface")
REFERENCE_METHODS = ("objective", "scenario_opt", "lin_min", "ef", "xbar_of",
                     "w_after", "infeasibility", "prox_gap")


def bench():
    with open(os.path.join(core.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def line(s, n=200):
    return 1 <= len(s) <= n and "\n" not in s and "\t" not in s


def test_shape_and_names():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(core.ROOT, "BENCHMARK.json")) < 65536
    assert b["paths"] == ["benchmarks"] and 1 <= b["run_seconds"] <= 51
    assert len(b["command"]) <= 32 and all(line(w) for w in b["command"])
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in b[group]]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names), names
    assert not {m["name"] for m in b["end_to_end"]} & {
        m["name"] for m in b["per_layer"]}
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert line(c["source"]) and line(c["why"]) and len(c["reduced"]) <= 16
        assert c["file"].startswith("benchmarks/")
        conf = json.load(open(os.path.join(core.ROOT, c["file"])))
        assert all(k in conf for k in c["reduced"])
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert line(w["why"]) and w["chips"] in (1, 4)
        assert NAME.match(w["traffic"])
        assert w["config"] in {c["name"] for c in b["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert {c["name"] for c in b["configs"]} == {w["config"]
                                                 for w in b["workloads"]}
    four = sum(w["chips"] == 4 for w in b["workloads"])
    assert four <= max(1, len(b["workloads"]) // 2)


def test_metrics():
    b = bench()
    cells = {w["name"] for w in b["workloads"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", cells)) <= cells
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES and line(m["layer"])
        assert m["moves"] in e2e
        # every cell it is read in reports the end-to-end metric it moves
        moved = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m.get("workloads", moved)) <= moved, m["name"]


def test_every_cell_has_its_files_and_reports_enough():
    b = bench()
    for w in b["workloads"]:
        cell = core.load_cell(w["name"])
        names = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2
        assert cell["per_layer"], w["name"]
        wl = cell["workload_file"]
        assert os.path.exists(os.path.join(
            core.BENCH_DIR, "drivers", wl["driver"] + ".py"))
        assert wl["checks"]
        for c in wl["checks"]:
            assert callable(checks.load_check(c["name"])), c["name"]
        ref = core.load_reference(cell["config_file"])
        assert all(callable(getattr(ref, m)) for m in REFERENCE_METHODS)
        for m in cell["per_layer"]:
            assert callable(core.load_reader(m["name"]))


def test_a_configuration_without_the_key_gets_the_two_stage_lp():
    ref = core.load_reference({})
    assert ref.__module__ == "benchmarks.references.two_stage_lp"
    named = core.load_reference({"reference": "two_stage_mip"})
    assert named.__module__ == "benchmarks.references.two_stage_mip"
    assert hasattr(named, "ef_int") and not hasattr(ref, "ef_int")
    assert not os.path.exists(os.path.join(core.BENCH_DIR, "harness",
                                           "reference.py"))
    assert not hasattr(checks, "CHECKS")


def test_a_name_with_no_file_says_which_files_there_are():
    with pytest.raises(FileNotFoundError, match="prox_gap_rel"):
        checks.load_check("no_such_check")
    with pytest.raises(FileNotFoundError, match="two_stage_lp"):
        core.load_reference({"reference": "no_such_reference"})
    # only a per-layer metric shares a file by its stem
    with pytest.raises(FileNotFoundError):
        byname.load("checks", "prox_gap_rel.wheel", core.BENCH_DIR, "value")


def test_a_split_metric_shares_the_reader_named_for_its_stem():
    a, b = (core.load_reader("idle_pct." + k) for k in ("wheel", "serve"))
    assert a.__code__.co_filename == b.__code__.co_filename
    assert a.__code__.co_filename.endswith("layer_metrics/idle_pct.py")


def test_an_unknown_device_kind_is_an_error(monkeypatch):
    import jax

    class Dev:
        platform, device_kind = "tpu", "TPU v9 imaginary"

    monkeypatch.setattr(jax, "devices", lambda: [Dev()])
    try:
        core.device_info(1)
    except KeyError as e:
        assert "not in peaks.json" in str(e)
    else:
        raise AssertionError("an unknown device kind must be an error")
    Dev.device_kind = "TPU v5 lite"
    assert core.device_info(1)["kind"] == "TPU v5 lite"
    try:
        core.device_info(4)
    except core.NoChip:
        pass
    else:
        raise AssertionError("fewer chips than the cell asks for must fail")
