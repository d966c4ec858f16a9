"""CPU rehearsals: each driver kind tiny, end to end, through the same
``run_cell`` the command line calls.  The tests put their own look for a
device in the harness's place and lay a tiny deployment over the cell's
files (``monkeypatch``): the command line can do neither, so a run from it
needs the chip and runs the files as committed.

Also kept here, as the contract of ``correct`` asks: the control (the
program computed one precision below the one the configuration states)
comes out as not correct, and so does a run whose timed path is broken
underneath.  The sizes are what a test run can hold; the readings on the
chip at the cells' own sizes are in PERF.md.
"""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from benchmarks.drivers import wheel as wheel_driver
from benchmarks.harness import checks, core

ROOT = core.ROOT
F64 = {"dtype": "float64", "eps_abs": 1e-8, "eps_rel": 1e-8}
FARMER = {"num_scens": 30, "creator_kwargs": {"crops_multiplier": 2},
          "solver_options": F64}
# limits for the tiny float64 deployments of this file: what float64 meets
# with room and float32, the precision below it, does not
TIGHT = [{"name": "iter0_obj_median_rel", "limit": 1e-10},
         {"name": "w_update_rel", "limit": 1e-9},
         {"name": "eobj_vs_ef_rel", "limit": 1e-2},
         {"name": "nonfinite", "limit": 0}]


def loose(cell):
    """The cell's own checks with limits for a tiny deployment and a window
    of a second or two (the committed limits are for the chip at size)."""
    wide = {"eobj_vs_ef_rel": 5e-2, "xbar_spread_rel": 5e-2,
            "prox_gap_rel": 0.5, "feas_rel": 5e-2,
            "iter0_obj_median_rel": 1e-2, "iter0_obj_worst_rel": 1e-2}
    checks_ = core.load_cell(cell)["workload_file"]["checks"]
    return [dict(c, limit=wide.get(c["name"], c["limit"])) for c in checks_]


@pytest.fixture
def run(monkeypatch):
    """``run_cell`` with the look for a chip skipped and ``config`` /
    ``workload`` laid over the cell's files."""
    def any_device(chips, bench_dir=None):
        import jax

        d = jax.devices()[0]
        return {"platform": d.platform, "kind": d.device_kind,
                "count": len(jax.devices())}

    monkeypatch.setattr(core, "device_info", any_device)
    load = core.load_cell

    def go(cell, trace=False, seconds=2.0, config=None, workload=None, **kw):
        def laid_over(name, *a):
            c = load(name, *a)
            c["config_file"] = dict(c["config_file"], **(config or {}))
            c["workload_file"] = dict(c["workload_file"], **(workload or {}))
            return c

        monkeypatch.setattr(core, "load_cell", laid_over)
        return core.run_cell(cell, seed=2**31 + 77, seconds=seconds,
                             trace=trace, t_start=time.monotonic(), **kw)

    return go


def bad(line):
    return {k: v for k, v in line["checks"].items() if not v["ok"]}


def values(line):
    return {k: v["value"] for k, v in line["checks"].items()}


def test_farmer_wheel_tiny_end_to_end(run):
    line = run("farmer_cm4_s1000.wheel", config=FARMER,
               workload={"warmup_iterations": 5,
                         "checks": loose("farmer_cm4_s1000.wheel")})
    assert line["correct"], bad(line)
    assert set(line["metrics"]) == {"hub_iter_s", "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["metrics"]["hub_iter_s"]["value"] == pytest.approx(
        line["window_s"] / line["attempted"])
    assert line["window_s"] >= 2.0
    assert list(line)[-1] == "checks"            # the compared numbers last
    assert line["device"]["platform"] == "cpu"   # named for what it ran on


def test_traced_run_on_the_cpu_prints_no_device_metric(run):
    line = run("farmer_cm4_s1000.wheel", trace=True, config=FARMER,
               workload={"warmup_iterations": 5, "trace_seconds": 1,
                         "checks": loose("farmer_cm4_s1000.wheel")})
    assert line["correct"], bad(line)
    got = set(line["metrics"])
    assert {"mega_iter_pct", "host_syncs_per_iter", "window_compile_s",
            "host_rescued_iter0"} <= got
    assert not {"idle_pct.wheel", "device_ms_per_iter"} & got
    assert "busy_s" not in line["device"] and "breakdown" not in line


def test_every_single_hub_step_of_the_window_is_compared(run, monkeypatch):
    seen = []
    load = checks.load_check

    def counting(name, *a):
        def value(ev, p):
            if name == "prox_gap_rel":
                seen.append((len(checks._steps(ev)), len(ev["watch"].steps)))
            return load(name, *a)(ev, p)

        return value

    monkeypatch.setattr(checks, "load_check", counting)
    line = run("farmer_cm4_s1000.wheel",
               config=dict(FARMER, solver_options=dict(F64, megastep=1)),
               workload={"warmup_iterations": 5, "checks": [
                   # a tiny deployment's first steps leave a row stalled
                   {"name": "prox_gap_rel", "limit": 0.5, "n_check": 4},
                   TIGHT[1], TIGHT[3]]})
    v = values(line)
    assert line["correct"], bad(line)
    assert v["w_update_rel"] is not None and v["prox_gap_rel"] is not None
    # megastep 1: every hub iteration is a single step; those of set-up
    # (before the window opened) are left out
    in_window, all_steps = seen[0]
    assert in_window == line["attempted"] and all_steps > in_window


def test_nothing_to_compare_is_not_correct():
    ev = [{"outer": float("nan"), "inner": float("inf"), "ef": 1.0}]
    ok, rows = checks.decide(ev, [{"name": "outer_over_ef_rel", "limit": 1}])
    assert not ok and rows[0]["value"] is None
    # a bound that no spoke returned has nothing to be compared with
    ok, rows = checks.decide(ev, [{"name": "inner_under_ef_rel", "limit": 1,
                                   "absent": "skip"}])
    assert ok and rows[0]["value"] is None
    assert checks.decide([], [])[0] is False


def test_served_tiny_end_to_end(run):
    conf = dict(FARMER, max_iterations=12)
    line = run("farmer_cm4_s1000.serve1", seconds=1.0, config=conf,
               workload={"checks": loose("farmer_cm4_s1000.serve1")})
    assert line["correct"], bad(line)
    assert set(line["metrics"]) == {"request_s", "ttfi_s", "request_rate",
                                    "setup_s"}
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert line["attempted"] == 1 and line["failed"] == 0
    # one client, closed loop: the rate repeats the request time
    assert m["request_rate"] == pytest.approx(1.0 / m["request_s"], rel=0.02)
    assert 0 < m["ttfi_s"] < m["request_s"]
    assert m["request_s"] > 30.0                 # the server's own linger


def test_control_one_precision_lower_is_not_correct(run):
    """float32 for float64: the step that would tempt a later PR."""
    sound = run("farmer_cm4_s1000.wheel",
                config=dict(FARMER, solver_options=dict(F64, megastep=1)),
                workload={"warmup_iterations": 5, "checks": TIGHT})
    assert sound["correct"], sound["checks"]
    lower = run("farmer_cm4_s1000.wheel",
                config=dict(FARMER, solver_options={
                    "dtype": "float32", "eps_abs": 1e-8, "eps_rel": 1e-8,
                    "megastep": 1}),
                workload={"warmup_iterations": 5, "checks": TIGHT})
    assert not lower["correct"], lower["checks"]
    assert values(lower)["iter0_obj_median_rel"] > 3 * values(sound)[
        "iter0_obj_median_rel"]


def _frozen_w(hub_dict, spokes):
    """Break the timed path: a hub whose dual update returns its state
    unchanged."""
    base = hub_dict["opt_class"]

    class FrozenW(base):
        def Update_W(self, verbose=False):
            self._bump_state_version()

    FrozenW.__name__ = base.__name__
    hub_dict["opt_class"] = FrozenW


def test_a_step_that_leaves_its_state_unchanged_is_not_correct(
        run, monkeypatch):
    build = wheel_driver.build_wheel

    def broken(*a):
        out = build(*a)
        _frozen_w(out[0], out[1])
        return out

    monkeypatch.setattr(wheel_driver, "build_wheel", broken)
    line = run("farmer_cm4_s1000.wheel",
               config=dict(FARMER, solver_options=dict(F64, megastep=1)),
               workload={"warmup_iterations": 5,
                         "checks": loose("farmer_cm4_s1000.wheel")})
    assert not line["correct"]
    bad = {k for k, v in line["checks"].items() if not v["ok"]}
    assert "w_update_rel" in bad, line["checks"]


def test_command_line_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "farmer_cm4_s1000.wheel", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


INCUMBENT = [{"name": "incumbent_infeas_rel", "limit": 1e-3},
             {"name": "incumbent_frac", "limit": 1e-6},
             {"name": "incumbent_nonant_spread", "limit": 1e-6},
             {"name": "inner_vs_incumbent_rel", "limit": 1e-6}]


@pytest.mark.parametrize("cell,over", [
    ("farmer_cm4_s1000.wheel", {"warmup_iterations": 5}),
    ("farmer_cm4_s1000.serve1", {"request_options": {"linger_secs": 0.0}})])
def test_the_incumbent_is_read_from_the_spoke_that_kept_it(run, cell, over):
    """A tiny float64 farmer wheel does get an inner bound: the evidence
    holds the point it is the price of, solo and served, and the four
    checks of the incumbent read it (the farmer has no integer column, so
    under ``two_stage_mip`` its fractionality is 0, and under the default
    reference, which reads no ``is_int``, there is nothing to compare)."""
    skip = dict(INCUMBENT[1], absent="skip")
    line = run(cell, seconds=1.0,
               config=dict(FARMER, max_iterations=12),
               workload=dict(over, checks=[INCUMBENT[0], skip] + INCUMBENT[2:]))
    assert line["correct"], bad(line)
    v = values(line)
    assert v["incumbent_frac"] is None
    assert all(v[c["name"]] is not None for c in INCUMBENT if c is not
               INCUMBENT[1])
    line = run(cell, seconds=1.0,
               config=dict(FARMER, max_iterations=12,
                           reference="two_stage_mip"),
               workload=dict(over, checks=INCUMBENT))
    assert line["correct"], bad(line)
    assert values(line)["incumbent_frac"] == 0.0


def test_a_new_cell_configuration_and_metric_are_files_only(tmp_path, run):
    """A later PR adds a configuration, a cell, a per-layer metric, a
    reference and a check as new files and new entries of BENCHMARK.json:
    no file that is there changes."""
    root = tmp_path
    shutil.copytree(os.path.join(ROOT, "benchmarks"), root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))

    def files():
        return {os.path.relpath(os.path.join(d, f), root): open(
                    os.path.join(d, f), "rb").read()
                for d, _dirs, fs in os.walk(root / "benchmarks") for f in fs
                if "__pycache__" not in d}

    before = files()
    # a reference of its own: the default one with one more truth, which
    # only the new check asks for
    (root / "benchmarks/references/farmer_acres.py").write_text(
        'from benchmarks.references.two_stage_lp import Reference as _LP\n\n\n'
        'class Reference(_LP):\n'
        '    def acres(self):\n'
        '        return float(self.ub[0][self.nonant].max())\n')
    (root / "benchmarks/checks/xbar_over_acres.py").write_text(
        'from benchmarks.harness import checks as H\n\n\n'
        'def value(ev, spec):\n'
        '    if not H.ref_has(ev, "acres"):\n'
        '        return None\n'
        '    return float(ev["xbars"].sum(axis=1).max() / ev["ref"].acres())\n')
    conf = json.load(open(root / "benchmarks/configs/farmer_cm4_s1000.json"))
    conf.update(FARMER, num_scens=12, reference="farmer_acres")
    (root / "benchmarks/configs/farmer_tiny.json").write_text(json.dumps(conf))
    wl = json.load(open(root / "benchmarks/workloads/farmer_cm4_s1000.wheel.json"))
    wl.update(warmup_iterations=3, trace_seconds=1,
              checks=loose("farmer_cm4_s1000.wheel")
              + [{"name": "xbar_over_acres", "limit": 1.0 + 1e-6}])
    (root / "benchmarks/workloads/farmer_tiny.wheel.json").write_text(
        json.dumps(wl))
    (root / "benchmarks/layer_metrics/hub_bound_updates.py").write_text(
        'def read(obs):\n'
        '    return obs["counters"].get("hub.outer_bound_updates", 0.0)\n')
    bench["configs"].append({"name": "farmer_tiny", "source": "a test",
                             "file": "benchmarks/configs/farmer_tiny.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "farmer_tiny.wheel",
                               "config": "farmer_tiny", "traffic": "wheel",
                               "chips": 1, "why": "a test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and "farmer_cm4_s1000.wheel" in m["workloads"]:
            m["workloads"].append("farmer_tiny.wheel")
    bench["per_layer"].append({
        "name": "hub_bound_updates", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "bound pass and spokes",
        "moves": "hub_iter_s", "workloads": ["farmer_tiny.wheel"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    line = run("farmer_tiny.wheel", seconds=1.0, trace=True,
               root=str(root), bench_dir=str(root / "benchmarks"))
    assert line["correct"], bad(line)
    assert "hub_bound_updates" in line["metrics"]
    assert "mega_iter_pct" in line["metrics"]
    # the new check read the new reference's truth: the acres planted are
    # the acres there are
    assert 0.5 < values(line)["xbar_over_acres"] <= 1.0 + 1e-6
    after = files()
    assert {p: after[p] for p in before} == before
    assert len(after) == len(before) + 5
    # under a reference that lacks the method the new check has nothing to
    # compare, and that is not correct
    ok, rows = checks.decide(
        [{"ref": core.load_reference({})}],
        [{"name": "xbar_over_acres", "limit": 1}], str(root / "benchmarks"))
    assert not ok and rows[0]["value"] is None
