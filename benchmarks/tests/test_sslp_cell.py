"""The cell ``sslp_10_50_2000.wheel``: rehearsed tiny on the CPU through the
``run_cell`` the command line calls (the tests' own look for a device in the
harness's place, a small scenario count laid over the configuration), the
reference it names held to the interface, and each per-layer reader it
brings on a recorded set of counters.  Sizes and limits here are a test's;
the readings on the chip at the cell's own size are in PERF.md (PR 32).
"""

import numpy as np
import pytest

from benchmarks.harness import core
# the rehearsals' fixture: ``run_cell`` with the tests' own look for a device
# and ``config`` / ``workload`` laid over the cell's files
from test_rehearsal import run  # noqa: F401

CELL = "sslp_10_50_2000.wheel"
NEW_METRICS = ("dive_rounds_per_candidate", "host_milp_pct",
               "host_milp_rows_per_pass", "dive_s_per_candidate")
# a tiny float32 deployment's solves stand further from HiGHS than the
# chip's at size, and a window of seconds holds a few steps
WIDE = {"iter0_obj_median_rel": 1e-2, "iter0_obj_worst_rel": 1e-1,
        "prox_gap_rel": 0.5, "feas_rel": 5e-2, "xbar_spread_rel": 1.0,
        "incumbent_infeas_rel": 1e-2, "incumbent_nonant_spread": 1e-4,
        "incumbent_frac": 1e-4, "inner_vs_incumbent_rel": 1e-4}


def tiny(trace):
    """Arguments for the shared ``run`` fixture: six scenarios, a short
    warm-up, the cell's own checks at a test's limits."""
    checks = core.load_cell(CELL)["workload_file"]["checks"]
    return dict(
        cell=CELL, trace=trace, config={"num_scens": 6},
        workload={"warmup_iterations": 3, "trace_seconds": 1, "checks": [
            dict(ch, limit=WIDE.get(ch["name"], ch["limit"]),
                 **({"n_check": 4} if "n_check" in ch else {}))
            for ch in checks]})


def test_sslp_wheel_tiny_end_to_end(run):
    line = run(**tiny(trace=False))
    bad = {k: v for k, v in line["checks"].items() if not v["ok"]}
    assert line["correct"], bad
    assert set(line["metrics"]) == {"hub_iter_s", "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0
    # an inner bound came, and the checks of the incumbent and of the two
    # bounds each compared something: none of them may be skipped
    assert line["notes"]["inner"] is not None
    for name in ("incumbent_infeas_rel", "incumbent_frac",
                 "incumbent_nonant_spread", "inner_vs_incumbent_rel",
                 "outer_over_inner_rel"):
        assert line["checks"][name]["value"] is not None, name
    wl = core.load_cell(CELL)["workload_file"]
    assert not any(c.get("absent") for c in wl["checks"])


def test_sslp_wheel_traced_reports_the_new_per_layer_metrics(run):
    # a window long enough to hold a whole dive of the tiny batch
    line = run(seconds=8.0, **tiny(trace=True))
    got = set(line["metrics"])
    assert set(NEW_METRICS) <= got, got
    assert {"mega_iter_pct", "spoke_passes_per_iter", "window_compile_s",
            "rescued_rows_per_iter"} <= got
    # nothing of the device is printed from a CPU
    assert not {"idle_pct.wheel", "device_ms_per_iter"} & got
    assert "refresh_lanes_pct" not in got and "host_rescued_iter0" not in got
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert 1.0 <= m["dive_rounds_per_candidate"] <= 12.0
    assert m["dive_s_per_candidate"] > 0.0
    assert 0.0 <= m["host_milp_pct"] <= 100.0
    assert m["host_milp_rows_per_pass"] >= 0.0


def test_the_configuration_names_two_stage_mip_and_gets_its_interface():
    cell = core.load_cell(CELL)
    assert cell["config_file"]["reference"] == "two_stage_mip"
    ref = core.load_reference(cell["config_file"])
    assert ref.__module__ == "benchmarks.references.two_stage_mip"
    for method in ("objective", "scenario_opt", "lin_min", "ef", "xbar_of",
                   "w_after", "infeasibility", "prox_gap", "int_min",
                   "ef_int"):
        assert callable(getattr(ref, method)), method
    # built from the creator at the cell's shapes: is_int is what the
    # creator marks (10 sites and 500 assignments; the overflow is not)
    import importlib

    module = importlib.import_module(
        "tpusppy.models." + cell["config_file"]["model"])
    kw = dict(cell["config_file"]["creator_kwargs"], seedoffset=5)
    r = ref(module, module.scenario_names_creator(3), kw)
    assert (r.n, r.m, r.nonant.size) == (520, 60, 10)
    assert int(r.is_int.sum()) == 510 and not r.is_int[-10:].any()
    assert r.ef_int() is None or r.S * r.n <= 5000   # not at a timed size


# the window's registry deltas of a tiny traced run (CPU, PR 32), cut to
# what the four readers read, and the same with what this PR adds taken out
RECORDED = {
    "phase.spoke2.dive.count": 9.0, "phase.spoke2.dive.secs": 31.5,
    "phase.spoke2.pass.count": 3.0, "phase.spoke1.pass.count": 5.0,
    "phase.spoke2.retry_dive.count": 2.0, "phase.spoke2.retry_dive.secs": 4.0,
    "phase.spoke2.host_milp.count": 1.0, "phase.spoke2.host_milp.secs": 0.5,
    "xhat.dive_rounds": 45.0, "xhat.dive_wedged_rows": 7.0,
    "xhat.retry_rows": 56.0, "xhat.host_milp_rows": 6.0,
}
WANT = {"dive_rounds_per_candidate": 5.0, "dive_s_per_candidate": 3.5,
        "host_milp_pct": 1.0, "host_milp_rows_per_pass": 2.0}


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_reader_on_recorded_counters(metric):
    read = core.load_reader(metric)
    obs = {"counters": dict(RECORDED), "window_s": 50.0, "iterations": 40}
    assert read(obs) == pytest.approx(WANT[metric])
    # the parent's program has neither the phases nor the counters: nothing
    # to read, and the line leaves the metric out
    parent = {k: v for k, v in RECORDED.items()
              if not k.startswith("xhat.") and ".dive." not in k
              and ".retry_dive." not in k and ".host_milp." not in k}
    assert read({"counters": parent, "window_s": 50.0,
                 "iterations": 40}) is None


def test_host_tier_readers_read_zero_where_no_row_reached_the_host():
    counters = {k: v for k, v in RECORDED.items() if "host_milp" not in k}
    obs = {"counters": counters, "window_s": 50.0, "iterations": 40}
    assert core.load_reader("host_milp_pct")(obs) == 0.0
    assert core.load_reader("host_milp_rows_per_pass")(obs) == 0.0
    assert np.isfinite(core.load_reader("dive_rounds_per_candidate")(obs))
    # a window that holds dives and the end of no pass counts as one pass
    obs["counters"].update({"phase.spoke2.pass.count": 0.0,
                            "xhat.host_milp_rows": 4.0})
    assert core.load_reader("host_milp_rows_per_pass")(obs) == 4.0
