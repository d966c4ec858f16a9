"""Read, on the chip and in one process, what the limits of ``correct`` are
set from: every number compared, for sound runs of the program over many
seeds and for the control (the program one precision lower: its own
``matmul_precision: high`` path, three passes for float32 at ``highest``).

    chiprun -- python3 benchmarks/tests/chip_readings.py \\
        --cell farmer_cm4_s1000.wheel --seconds 10 \\
        --seeds 2147485001,2147485002 --control-seeds 2147485001 \\
        --out chiprun_out/readings_wheel.jsonl

Each seed drives the cell's own driver at the cell's own size through a
window of ``--seconds`` (a short one: the numbers need no measured window,
only the cell's load), then ``checks.decide`` on what it left behind.  One
line per seed: the numbers, and the spread of the per-row readings they
are the worst or the median of, the per-step gaps of ``--n-check`` drawn
rows among them.  ``--request-options`` lays over the served
workload's ``request_options`` (``{"linger_secs": 0}`` spares the 30 s in
which no iterate changes).  Not run by the benchmark or by pytest.
"""

import argparse
import contextlib
import importlib
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
CONTROL = {"matmul_precision": "high"}


def spread(a):
    a = np.asarray(a, float)
    q = np.quantile(a, [0.25, 0.5, 0.75, 0.9, 0.95, 0.99, 1.0])
    return {"q25_50_75_90_95_99_100": [float(v) for v in q],
            "mean": float(a.mean())}


def one(core, checks, cell, seed, seconds, control, request_options,
        n_check):
    conf, wl = dict(cell["config_file"]), dict(cell["workload_file"])
    if n_check:
        wl["checks"] = [dict(c, n_check=n_check) if "n_check" in c else c
                        for c in wl["checks"]]
    if control:
        conf["solver_options"] = dict(conf["solver_options"], **CONTROL)
    if request_options is not None:
        wl["request_options"] = request_options
    driver = importlib.import_module("benchmarks.drivers." + wl["driver"])
    t0 = time.monotonic()
    obs = driver.run({
        "cell": cell["name"], "config": conf, "workload": wl,
        "seed": seed, "data_seed": core.data_seed(seed),
        "seconds": seconds, "trace": False, "t_start": t0,
        "bench_dir": core.BENCH_DIR})
    t1 = time.monotonic()
    correct, rows = checks.decide(obs["evidence"], wl["checks"])
    per_wheel = []
    for ev in obs["evidence"]:
        steps = checks._steps(ev)
        n = max([c.get("n_check", 0) for c in wl["checks"]
                 if c["name"] == "prox_gap_rel"] or [16])
        per_wheel.append({
            "iter0": spread(checks._iter0_gaps(ev)),
            "prox_gaps": checks.prox_gaps(ev, n).tolist(),
            "steps": [st["iteration"] for st in steps],
            "rescued0": int(ev["watch"].rescued0.sum())})
    return {"cell": cell["name"], "seed": seed, "control": bool(control),
            "correct": bool(correct and obs["failed"] == 0),
            "numbers": {r["name"]: r["value"] for r in rows},
            "failing": [r["name"] for r in rows if not r["ok"]],
            "end_to_end": obs["end_to_end"], "attempted": obs["attempted"],
            "wheels": per_wheel, "drive_s": t1 - t0,
            "reference_s": time.monotonic() - t1}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--request-options", default=None)
    ap.add_argument("--n-check", type=int, default=0,
                    help="rows drawn for the per-step gaps (the cell's own "
                         "where 0)")
    ap.add_argument("--config", default=None,
                    help="JSON laid over the configuration (a rehearsal)")
    ap.add_argument("--no-chip", action="store_true",
                    help="skip the look for a chip (a rehearsal)")
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    ropts = None if args.request_options is None else json.loads(
        args.request_options)
    with contextlib.redirect_stdout(sys.stderr):
        import jax

        from benchmarks.harness import checks, core
        from tpusppy.solvers import aot

        cell = core.load_cell(args.cell)
        if args.config:
            cell["config_file"] = dict(cell["config_file"],
                                       **json.loads(args.config))
        if not args.no_chip:
            core.device_info(cell["chips"])
        aot.arm_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        plan = [(int(s), False) for s in args.seeds.split(",") if s] + [
            (int(s), True) for s in args.control_seeds.split(",") if s]
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "a") as out:
            for seed, control in plan:
                try:
                    line = one(core, checks, cell, seed, args.seconds,
                               control, ropts, args.n_check)
                except Exception as e:       # a control may crash: recorded
                    import traceback

                    line = {"cell": args.cell, "seed": seed,
                            "control": control, "error": repr(e),
                            "traceback": traceback.format_exc()[-2000:]}
                out.write(json.dumps(line) + "\n")
                out.flush()
                print("READING", json.dumps(line), file=sys.stderr,
                      flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
