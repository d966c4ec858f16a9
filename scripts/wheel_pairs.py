"""Untraced runs of one wheel cell from two checkouts, alternating, with what
the benchmark's result line leaves out of an untraced run (PERF.md section 6,
PR 43: the slow run).

One process a run (a chip belongs to one process): this script starts itself
with ``--one`` for every run, with the checkout to import the benchmark and
the program from.  A run is the benchmark's own (``benchmarks/harness/core``
and the cell's driver, the cell's files, its checks); besides the result
line's numbers it prints the hub iterations in the window, the window's
seconds, the seconds between the hub's last boundary and the window's close
(the wheel's own ending and its tear-down are inside a window that the wheel
ends itself), the per-layer metrics that read the program's counters, the
compile seconds inside the window, whether an inner bound ever arrived, and
the window's outcome counters and phases (``solve.*``, ``xhat.*``,
``phase.*``).

Runs go parent, change, change, parent, ...: each seed once a side, the first
run of a side its cold one (each checkout keeps its own ``.jax_cache``).

Usage (the chip):
  python scripts/wheel_pairs.py --parent _ab/parent --change _ab/change \\
      --pairs 12 --seed0 4300000101 [--workload farmer_cm4_s1000.wheel]
Every run's line is appended to ``chiprun_out/wheel_pairs.jsonl`` as it ends.
"""

import time

T_START = time.monotonic()

import argparse                     # noqa: E402
import contextlib                   # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import statistics                   # noqa: E402
import subprocess                   # noqa: E402
import sys                          # noqa: E402

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COUNTER_METRICS = ("legacy_iter_s", "mega_window_s", "hub_blocked_pct",
                   "spoke_passes_per_iter", "rescued_rows_per_iter",
                   "hub_sync_ms_per_iter", "refresh_lanes_pct",
                   "refresh_lanes_inverse_pct", "hub_sweeps_per_iter",
                   "spoke_sweeps_per_iter", "sweep_budget_spent_pct",
                   "solve_rows_done_pct", "sweep_width_pct",
                   "sweep_kernel_checkpoint_pct")


def one(root, workload, seed, seconds):
    """One untraced run of ``workload`` from the checkout ``root``."""
    import importlib

    sys.path.insert(0, root)
    with contextlib.redirect_stdout(sys.stderr):
        from benchmarks.harness import core
        from benchmarks.harness import checks as _checks

        assert core.ROOT == root, (core.ROOT, root)
        cell = core.load_cell(workload)
        conf, wl = cell["config_file"], cell["workload_file"]
        device = core.device_info(cell["chips"])

        import jax

        from tpusppy.solvers import aot

        aot.arm_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        driver = importlib.import_module("benchmarks.drivers." + wl["driver"])
        obs = driver.run({
            "cell": workload, "config": conf, "workload": wl,
            "seed": int(seed), "data_seed": core.data_seed(seed),
            "seconds": float(seconds), "trace": False, "t_start": T_START,
            "bench_dir": core.BENCH_DIR})
        correct, rows = _checks.decide(obs["evidence"], wl["checks"])
    watch = obs["evidence"][0]["watch"]
    t_close = T_START + obs["end_to_end"]["setup_s"] + obs["window_s"]
    line = {
        "root": os.path.relpath(root, HERE), "seed": int(seed),
        "device": device["kind"], "correct": bool(correct),
        "hub_iter_s": obs["end_to_end"]["hub_iter_s"],
        "iterations": obs["iterations"], "window_s": obs["window_s"],
        "tail_s": t_close - watch.marks[-1][0],
        "body_iter_s": (watch.marks[-1][0] - t_close + obs["window_s"])
        / obs["iterations"],
        "setup_s": obs["end_to_end"]["setup_s"],
        "compile_s": obs["compile_s"],
        "inner": obs["notes"]["inner"], "outer": obs["notes"]["outer"],
        "teardown_s": sum(v for k, v in obs["counters"].items()
                          if k.startswith("phase.")
                          and k.endswith(".teardown.secs")),
        "refreshes": {k.split(".")[1]: v for k, v in obs["counters"].items()
                      if k.startswith("phase.")
                      and k.endswith(".refresh.count")},
        "checks": {r["name"]: r["value"] for r in rows},
        # what the solves spent and how they ended, beside the phases'
        # seconds (PERF.md section 6, PR 44): the window's deltas
        "counters": {k: v for k, v in sorted(obs["counters"].items())
                     if v and k.startswith(("solve.", "xhat.", "phase."))},
    }
    obs["workload"] = wl
    for name in COUNTER_METRICS:
        try:
            value = core.load_reader(name)(obs)
        except FileNotFoundError:       # a reader this checkout lacks
            value = None
        line[name] = value
    print(json.dumps(line), flush=True)


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--one", action="store_true")
    ap.add_argument("--root")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--parent")
    ap.add_argument("--change")
    ap.add_argument("--pairs", type=int, default=12)
    ap.add_argument("--seed0", type=int, default=4300000101)
    ap.add_argument("--workload", default="farmer_cm4_s1000.wheel")
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--out", default="wheel_pairs.jsonl")
    ap.add_argument("--deadline", type=float, default=None,
                    help="start no new pair this many seconds after the "
                         "script started (a chip call has a time limit)")
    args = ap.parse_args()
    if args.one:
        return one(os.path.abspath(args.root), args.workload, args.seed,
                   args.seconds)

    out_dir = os.path.join(HERE, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    sides = {"parent": os.path.abspath(args.parent),
             "change": os.path.abspath(args.change)}
    lines = {"parent": [], "change": []}
    for i in range(args.pairs):
        if (args.deadline is not None
                and time.monotonic() - T_START > args.deadline):
            break
        order = ("parent", "change") if i % 2 == 0 else ("change", "parent")
        for side in order:
            t0 = time.monotonic()
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--one",
                 "--root", sides[side], "--seed", str(args.seed0 + i),
                 "--workload", args.workload, "--seconds",
                 str(args.seconds)],
                cwd=sides[side], capture_output=True, text=True)
            last = (proc.stdout.strip().splitlines() or [""])[-1]
            try:
                line = json.loads(last)
            except ValueError:
                line = {"failed": proc.returncode,
                        "stderr": proc.stderr[-2000:]}
            line.update(side=side, seed=args.seed0 + i,
                        wall_s=time.monotonic() - t0)
            lines[side].append(line)
            print(json.dumps(line), flush=True)
            with open(os.path.join(out_dir, args.out), "a") as f:
                f.write(json.dumps(line) + "\n")
    summary = {}
    for side, rows in lines.items():
        vals = [r["hub_iter_s"] for r in rows if "hub_iter_s" in r]
        if len(vals) >= 2:
            summary[side] = {"n": len(vals), "min": min(vals),
                             "median": statistics.median(vals),
                             "max": max(vals), "spread": spread(vals)}
    pairs = [(p["hub_iter_s"], c["hub_iter_s"])
             for p, c in zip(lines["parent"], lines["change"])
             if "hub_iter_s" in p and "hub_iter_s" in c]
    summary["pairs_won"] = [sum(c < p for p, c in pairs), len(pairs)]
    if "parent" in summary and "change" in summary:
        summary["every_change_under_every_parent"] = (
            summary["change"]["max"] < summary["parent"]["min"])
    print(json.dumps({"summary": summary}), flush=True)
    with open(os.path.join(out_dir, args.out), "a") as f:
        f.write(json.dumps({"summary": summary}) + "\n")


if __name__ == "__main__":
    main()
