"""Record what ``tests/test_replay.py`` replays: the evidence that one tiny
farmer wheel and one tiny served request leave behind (CPU, float64), with
the value every check gives on it.

    JAX_PLATFORMS=cpu JAX_ENABLE_X64=1 python3 benchmarks/tests/record_replay.py

writes ``data/replay_<kind>.npz`` (the arrays, float64 as they were) and
``data/replay_<kind>.json`` (what rebuilds the reference, the scalars, and
the values, which JSON's shortest round-trip floats keep bit for bit).  The
files in the repo were written by the harness as PR 29 left it (this script
with ``reference.RefData`` and the table ``checks.CHECKS`` in the place of
``load_reference`` and ``decide``'s files), BEFORE references and checks
became files found by name (PR 31): the replay holds the by-name checks to
those floats.  Record again only where a check's arithmetic is meant to
change, and say so.  Not run by the benchmark or by pytest.
"""

import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
DATA = os.path.join(HERE, "data")

F64 = {"dtype": "float64", "eps_abs": 1e-8, "eps_rel": 1e-8}
TINY = {"num_scens": 12, "creator_kwargs": {"crops_multiplier": 2},
        "solver_options": F64}
NAMES = ["iter0_obj_median_rel", "iter0_obj_worst_rel", "xbar_rel",
         "w_mean_rel", "feas_rel", "w_update_rel", "prox_gap_rel",
         "eobj_vs_ef_rel", "xbar_spread_rel", "outer_over_ef_rel",
         "inner_under_ef_rel", "outer_over_inner_rel", "nonfinite",
         "state_off_device", "record_bad"]
N_CHECK = 5
STEP_KEYS = ("W_prev", "xbars_prev", "x", "W", "xbars")


def specs(ev):
    return [{"name": n, "limit": 1.0, "n_check": N_CHECK} for n in NAMES
            if n != "record_bad" or ev.get("record") is not None]


def store(kind, ev, ref_args, values):
    steps = ev["watch"].steps
    arrays = {k: np.asarray(ev[k], float)
              for k in ("x", "W", "xbars", "rho")}
    arrays["x0"] = np.asarray(ev["watch"].x0, float)
    for k in STEP_KEYS:
        arrays["steps_" + k] = np.stack([st[k] for st in steps])
    np.savez_compressed(os.path.join(DATA, f"replay_{kind}.npz"), **arrays)
    rec = ev.get("record")
    meta = {
        "ref": ref_args, "seed": ev["seed"],
        "first_iteration": ev.get("first_iteration", 0),
        "step_iterations": [st["iteration"] for st in steps],
        "outer": ev["outer"], "inner": ev["inner"],
        "device_leaves": list(ev["device_leaves"]),
        "record": None if rec is None else {
            k: rec[k] for k in ("status", "iters", "certified")},
        "iter_limit": ev.get("iter_limit"),
        "n_check": N_CHECK, "values": values}
    with open(os.path.join(DATA, f"replay_{kind}.json"), "w") as f:
        json.dump(meta, f, indent=1)


def keeping_args(cls):
    """``cls`` (a reference) that remembers what it was built from."""

    class Kept(cls):
        def __init__(self, module, names, creator_kwargs):
            super().__init__(module, names, creator_kwargs)
            self.ref_args = {"model": module.__name__.rsplit(".", 1)[-1],
                             "num_scens": len(names),
                             "creator_kwargs": dict(creator_kwargs)}

    return Kept


def main():
    import jax

    from benchmarks.harness import checks, core

    load_reference = core.load_reference
    core.load_reference = lambda *a: keeping_args(load_reference(*a))

    def any_device(chips, bench_dir=None):
        d = jax.devices()[0]
        return {"platform": d.platform, "kind": d.device_kind,
                "count": len(jax.devices())}

    core.device_info = any_device
    load, decide = core.load_cell, checks.decide
    kept = {}

    def keeping(evidence, wl_specs, *a, **kw):
        kept["evidence"] = evidence
        return decide(evidence, wl_specs, *a, **kw)

    checks.decide = keeping
    for kind, cell, seconds, conf, wl in (
            ("wheel", "farmer_cm4_s1000.wheel", 1.0, TINY,
             {"warmup_iterations": 3}),
            ("served", "farmer_cm4_s1000.serve1", 0.5,
             dict(TINY, max_iterations=12),
             {"request_options": {"linger_secs": 0.0}})):
        def laid_over(name, *a, conf=conf, wl=wl):
            c = load(name, *a)
            c["config_file"] = dict(c["config_file"], **conf)
            c["workload_file"] = dict(c["workload_file"], **wl, checks=[])
            return c

        core.load_cell = laid_over
        core.run_cell(cell, seed=2**31 + 31, seconds=seconds, trace=False,
                      t_start=time.monotonic())
        ev = kept["evidence"][0]
        # a few steps are enough, and keep the files small
        ev["watch"].steps = [
            st for st in ev["watch"].steps
            if st["iteration"] > ev.get("first_iteration", 0)][:4]
        _ok, rows = decide([ev], specs(ev))
        ref = ev["ref"]
        store(kind, ev, ref.ref_args, {r["name"]: r["value"] for r in rows})
        print(kind, "S", ref.S, "steps", len(ev["watch"].steps),
              {r["name"]: r["value"] for r in rows})


if __name__ == "__main__":
    main()
