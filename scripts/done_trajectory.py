"""When the rows of a dense batch finish, on the device it runs on (PERF.md
section 5, PR 45; outside every cell of the benchmark).

The dense engine's sweep loop (``admm._admm_core``) stops a row when the
program's own test passes it; how much of the batch is still unfinished, and
when, decides what a loop that narrows to those rows can save.  Builds the
batch of the benchmark's ``farmer_cm4_s1000`` cells (as
``scripts/refresh_split.py`` does), runs a plain PH loop on it (a refresh
at iteration 1 and every ``--refresh-every`` after it, frozen solves between,
rho and settings the configuration's) and prints JSON lines:

  refresh   rows done of S after restart 1 / 2 / 3 / 4 (``restarts`` 1-4,
            no polish: the loop's own ``done``), for the hub's prox
            objective and the Lagrangian's (W on, no prox), cold (iteration
            0) and warm (every later refresh)
  frozen    rows done after a budget of 100 / 250 / 500 / 1000 sweeps
            (``max_iter``) at the iterations of ``--probe``, the sweeps the
            whole solve ran, and (a tree that counts them) the sweeps below
            full width and the share of the full-width work done
  width     one sweep's device time at S = 128, 256, 512 and S rows of the
            same batch (``solve_batch_frozen``, its whole budget, median of
            ``--reps``)
  solve     the refresh and the frozen solve as the cell runs them, warm,
            in milliseconds (median of ``--reps``)
  split     (``--split``) one traced frozen solve at 256 and at S rows: the
            device's microseconds a checkpoint (one step of the sweep
            loop's ``while``) in the sweep kernel (``%fused_sweeps*`` on
            the trace's ``XLA Ops`` line) and in every other operation that
            ran as often, each by name
  linger    (``--linger``) what the sweeps after the test are worth: at the
            iterations of ``--probe`` the frozen solve again on the one
            all-or-nothing loop at every budget of ``LADDER``, each row's
            objective against a float64 solve of the same problem (a child
            of this script on the host's CPU) at 0, 64, ... sweeps after the
            budget that first passed it, the rows that pass and fail again,
            and the same error for the tree's own solve (narrowed, where it
            narrows) and the one loop's; at a refresh the tree's warm
            refresh against the one loop's from the same start

``--wheel`` instead runs the benchmark's ``farmer_cm4_s1000.wheel`` once
(its own driver and window) and prints, megastep window by window, how
many rows of the last executed iteration were done (``done_s`` of the
packed measurement, fetched already).

``--root <checkout>`` imports the program from another tree (a parent
unpacked under ``_ab/``).  ``--width 512`` (a measurement's, not the
program's: it has no such setting) replaces ``admm._rung_width``'s answer for
this run, to weigh another rung against the one the tree takes;
``--quick`` leaves the two tables out and times the solves only.

Usage (the chip): python scripts/done_trajectory.py [--root _ab/parent]
                  python scripts/done_trajectory.py --wheel [--root ...]
"""

import time

T_START = time.monotonic()

import argparse                     # noqa: E402
import dataclasses                  # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import statistics                   # noqa: E402
import subprocess                   # noqa: E402
import sys                          # noqa: E402

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BUDGETS = (100, 250, 500, 1000)
WIDTHS = (128, 256, 512)
LADDER = tuple(range(32, 1000, 32)) + (1000,)
AFTER = (0, 64, 128, 192, 256, 384, 512)


def say(**row):
    print(json.dumps(row), flush=True)


def median_ms(fn, reps):
    import jax

    jax.block_until_ready(fn())
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        out.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(out)


def width_of(sol):
    """The narrowing's counters of a solution, where the tree has them."""
    import numpy as np

    if getattr(sol, "swept", None) is None:
        return {}
    S = sol.iters.shape[0]
    full = int(sol.iters[0]) * S
    return {"narrow_sweeps": int(np.max(sol.narrow)),
            "width_pct": 100.0 * float(np.sum(sol.swept)) / max(full, 1)}


def one_loop(admm, fn):
    """``fn()`` with the tree's sweep loop held to the one all-or-nothing
    loop (a parent's tree has no other); the caller marks the settings so
    that the program is traced anew."""
    if not hasattr(admm, "_rung_width"):
        return fn()
    width = admm._rung_width
    admm._rung_width = lambda S, bs: 0
    try:
        return fn()
    finally:
        admm._rung_width = width


def op_split(run, out_dir):
    """{operation: [runs, device ns]} of one traced ``run()``, from the
    ``XLA Ops`` line of the trace's device plane (an event's name is its
    whole HLO line: what stands before `` = `` is kept)."""
    import glob
    import shutil
    import tempfile

    import jax

    jax.block_until_ready(run())
    d = tempfile.mkdtemp(dir=out_dir)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    jax.block_until_ready(run())
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(d, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    ops = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if (not plane.name.startswith("/device:TPU:")
                or "SparseCore" in plane.name):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                c = ops.setdefault(ev.name.split(" = ", 1)[0][:80], [0, 0.0])
                c[0] += 1
                c[1] += float(ev.duration_ns)
    shutil.rmtree(d)
    return ops


def say_split(width, ops, check_every):
    """One ``split`` line: the kernel against the rest of a step.  An
    operation of the loop's body or condition ran once a checkpoint, as the
    kernel did (the condition once more); the ``while`` itself and what ran
    once a solve are left out."""
    calls = sum(c for name, (c, _) in ops.items() if "fused_sweeps" in name)
    if not calls:
        return say(split=width, kernel_calls=0,
                   ops={k: v for k, v in sorted(ops.items())})
    kernel = sum(ns for name, (_, ns) in ops.items()
                 if "fused_sweeps" in name)
    rest = {name: (c, ns) for name, (c, ns) in ops.items()
            if c >= calls and "fused_sweeps" not in name
            and not name.startswith("%while")}
    rest_ns = sum(ns for _, ns in rest.values())
    say(split=width, checkpoints=calls,
        kernel_us_per_checkpoint=kernel / calls / 1e3,
        rest_us_per_checkpoint=rest_ns / calls / 1e3,
        kernel_us_per_sweep=kernel / calls / 1e3 / check_every,
        rest_us_per_sweep=rest_ns / calls / 1e3 / check_every,
        rest_ops=len(rest),
        rest_by_op={name: [c, round(ns / calls / 1e3, 3)]
                    for name, (c, ns) in sorted(
                        rest.items(), key=lambda kv: -kv[1][1])},
        once={name: round(ns / 1e3, 1) for name, (c, ns) in ops.items()
              if (c < calls or name.startswith("%while")) and ns >= 50})


def quantiles(err):
    import numpy as np

    if not err.size:
        return None
    return [float(np.quantile(err, q)) for q in (0.5, 0.9, 0.99, 1.0)]


def reference(path, root):
    """The child: float64 answers to the problems of ``path`` on the host's
    CPU, and each recorded iterate's objective against them."""
    import numpy as np

    from tpusppy.solvers import admm
    from tpusppy.solvers.admm import ADMMSettings

    z = np.load(path)
    prob = tuple(z[k] for k in ("q", "q2", "A", "cl", "cu", "lb", "ub"))
    ref = admm.solve_batch(*prob, settings=ADMMSettings(dtype="float64"))
    q, q2 = prob[:2]
    obj = lambda x: np.einsum("sn,sn->s", q + 0.5 * q2 * x, x)
    best = obj(np.asarray(ref.x))
    err = lambda x: (np.abs(obj(np.asarray(x, np.float64)) - best)
                     / np.maximum(np.abs(best), 1.0))
    row = {"reference_done": int(np.count_nonzero(ref.done)),
           "quantiles": [0.5, 0.9, 0.99, 1.0]}
    for name in ("tree", "one_loop"):
        row[name] = quantiles(err(z[name]))
    if "ladder" in z:
        X, D, ladder = z["ladder"], z["done"], list(z["budgets"])
        passed = D.any(axis=0)
        first = D.argmax(axis=0)
        rows = np.flatnonzero(passed)
        step = ladder[1] - ladder[0]
        row.update(
            rows_passed=int(passed.sum()),
            rows_done_at_end=int(D[-1].sum()),
            # passed at one budget, not at a later one
            passed_then_failed=int(np.count_nonzero(
                [(~D[first[r]:, r]).any() for r in rows])),
            after=list(AFTER), rows=[], error=[])
        E = np.stack([err(x) for x in X])
        for d in AFTER:
            at = first[rows] + d // step
            # the last budget (1000) is no whole step: leave it out
            ok = at < len(ladder) - 1
            row["rows"].append(int(ok.sum()))
            row["error"].append(quantiles(E[at[ok], rows[ok]]))
        row["passed_rows_at_end"] = quantiles(E[-1, rows])
    print(json.dumps(dict(json.loads(str(z["label"])), **row)), flush=True)


def tables(args):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpusppy.ir import ScenarioBatch
    from tpusppy.models import farmer
    from tpusppy.solvers import admm
    from tpusppy.solvers.admm import ADMMSettings

    if args.width is not None:
        admm._rung_width = lambda S, bs: args.width if args.width < S else 0
    with open(os.path.join(HERE, "benchmarks", "configs",
                           "farmer_cm4_s1000.json")) as f:
        conf = json.load(f)
    S = args.scens
    kw = dict(conf["creator_kwargs"], num_scens=S, seedoffset=args.seed)
    b = ScenarioBatch.from_problems(
        [farmer.scenario_creator(nm, **kw)
         for nm in farmer.scenario_names_creator(S)])
    st = ADMMSettings(**conf["solver_options"])
    dt = st.jdtype()
    dev = jax.devices()[0]
    say(device=dev.device_kind, platform=dev.platform, S=S, n=b.num_vars,
        m=b.num_rows, settings=conf["solver_options"], reps=args.reps,
        tree=os.path.relpath(os.path.dirname(os.path.dirname(
            os.path.dirname(admm.__file__))), HERE),
        rung=(admm._rung_width(S, 128)
              if hasattr(admm, "_rung_width") else None))

    A, cl, cu, lb, ub = (jnp.asarray(v, dt)
                         for v in (b.A, b.cl, b.cu, b.lb, b.ub))
    idx = np.asarray(b.tree.nonant_indices)
    rho = float(conf["default_rho"])
    probs = np.asarray(b.probs)
    no_polish = dataclasses.replace(st, polish=False)

    def problem(W, xbar, prox):
        q, q2 = np.array(b.c), np.array(b.q2)
        q[:, idx] += W - prox * rho * xbar
        q2[:, idx] += prox * rho
        return (jnp.asarray(q, dt), jnp.asarray(q2, dt), A, cl, cu, lb, ub)

    def by_restart(prob, warm):
        return [int(np.count_nonzero(admm.solve_batch_factored(
            *prob, settings=dataclasses.replace(no_polish, restarts=r),
            warm=warm)[0].done)) for r in (1, 2, 3, 4)]

    def by_budget(prob, factors, warm):
        return [int(np.count_nonzero(admm.solve_batch_frozen(
            *prob, factors, settings=dataclasses.replace(st, max_iter=k),
            warm=warm).done)) for k in BUDGETS]

    # a field the traced programs do not read: another program of the same
    # mathematics, traced while the loop is held to one
    marked = dataclasses.replace(st, precision_guard=st.precision_guard + 1)
    asked = []

    def ask_reference(label, prob, **arrays):
        path = os.path.join(args.out, "linger_%d.npz" % len(asked))
        names = ("q", "q2", "A", "cl", "cu", "lb", "ub")
        np.savez(path, label=json.dumps(label), **arrays,
                 **{k: np.asarray(v, np.float64)
                    for k, v in zip(names, prob)})
        asked.append(path)

    def linger_frozen(it, prob, factors, warm, sol):
        runs = one_loop(admm, lambda: [admm.solve_batch_frozen(
            *prob, factors, settings=dataclasses.replace(marked, max_iter=k),
            warm=warm) for k in LADDER])
        ask_reference(
            {"linger": "frozen", "iteration": it}, prob,
            tree=np.asarray(sol.x), one_loop=np.asarray(runs[-1].x),
            ladder=np.stack([np.asarray(r.x) for r in runs]),
            done=np.stack([np.asarray(r.done) for r in runs]),
            budgets=np.asarray(LADDER))

    def linger_refresh(it, prob, warm, sol):
        wide = one_loop(admm, lambda: admm.solve_batch_factored(
            *prob, settings=marked, warm=warm)[0])
        ask_reference(
            {"linger": "refresh", "iteration": it,
             "tree_done": int(np.count_nonzero(sol.done)),
             "one_loop_done": int(np.count_nonzero(wide.done)),
             "tree_sweeps": int(sol.iters[0]),
             "one_loop_sweeps": int(wide.iters[0])}, prob,
            tree=np.asarray(sol.x), one_loop=np.asarray(wide.x))

    W = np.zeros((S, idx.size))
    xbar = np.zeros(idx.size)
    warm = lagr_warm = factors = None
    probe = set(args.probe)
    tables_on = not args.quick
    last = {}
    for it in range(max(probe | {2 * args.refresh_every + 1}) + 1):
        prob = problem(W, xbar, 1.0 if it else 0.0)
        # iteration 0 is the plain LP; the prox term then moves q2, which
        # the factors hold, so iteration 1 refreshes as the hub's does
        if it == 0 or (it - 1) % args.refresh_every == 0:
            start = "warm" if it else "cold"
            # iteration 0 is the plain LP (W = 0, no prox): the hub's Iter0
            # and the Lagrangian's first pass alike
            if tables_on:
                say(refresh="hub_prox" if it else "lp_iter0", start=start,
                    iteration=it, done_after_restart=by_restart(prob, warm))
            if it and tables_on:
                lagr = problem(W, xbar, 0.0)
                say(refresh="lagrangian", start="warm", iteration=it,
                    done_after_restart=by_restart(lagr, lagr_warm))
                lagr_warm = admm.solve_batch_factored(
                    *lagr, settings=st, warm=lagr_warm)[0].raw
            if it == args.refresh_every + 1 and tables_on:
                # the prox objective from nothing: what a spoke's first
                # refresh under a moved objective pays
                say(refresh="hub_prox", start="cold", iteration=it,
                    done_after_restart=by_restart(prob, None))
            t0 = time.perf_counter()
            sol, factors = admm.solve_batch_factored(
                *prob, settings=st, warm=warm)
            jax.block_until_ready(sol.x)
            if it <= 1:
                # the program's first run cold and its first run warm: a
                # trace and a compile each (a warm start is another program)
                say(first_call="refresh", iteration=it,
                    seconds=time.perf_counter() - t0)
            if it == 0:
                lagr_warm = sol.raw
            last["refresh"] = (prob, warm)
            say(solve="refresh", iteration=it, sweeps=int(sol.iters[0]),
                done=int(np.count_nonzero(sol.done)), **width_of(sol))
            if args.linger:
                linger_refresh(it, prob, warm, sol)
        else:
            if it in probe and tables_on:
                say(frozen=it, budgets=BUDGETS,
                    done_after_budget=by_budget(prob, factors, warm))
            t0 = time.perf_counter()
            sol = admm.solve_batch_frozen(*prob, factors, settings=st,
                                          warm=warm)
            jax.block_until_ready(sol.x)
            if "frozen" not in last:
                say(first_call="frozen", iteration=it,
                    seconds=time.perf_counter() - t0)
            last["frozen"] = (prob, factors, warm)
            if it in probe:
                say(solve="frozen", iteration=it, sweeps=int(sol.iters[0]),
                    done=int(np.count_nonzero(sol.done)), **width_of(sol))
                if args.linger:
                    linger_frozen(it, prob, factors, warm, sol)
        warm = sol.raw
        x = np.asarray(sol.x)[:, idx]
        xbar = probs @ x
        W = W + rho * (x - xbar)

    # the float64 answers, on the host's CPU while this process holds the
    # device
    for path in asked:
        subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--root", args.root,
             "--reference", path],
            env=dict(os.environ, JAX_PLATFORMS="cpu", JAX_ENABLE_X64="1"),
            check=False)
        os.remove(path)

    # the two solves as the cell runs them, warm, from the loop's last ones
    prob, w = last["refresh"]
    run = lambda: admm.solve_batch_factored(*prob, settings=st, warm=w)[0]
    say(solve="refresh", ms=median_ms(run, args.reps),
        sweeps=int(run().iters[0]), **width_of(run()))
    prob, factors, w = last["frozen"]
    run = lambda: admm.solve_batch_frozen(*prob, factors, settings=st,
                                          warm=w)
    say(solve="frozen", ms=median_ms(run, args.reps),
        sweeps=int(run().iters[0]),
        done=int(np.count_nonzero(run().done)), **width_of(run()))

    # one sweep's time by width: the first rows of the same batch, the LP
    # from nothing, its whole budget at that width (a tree whose loop
    # narrows is held to the one loop here)
    if hasattr(admm, "_rung_width"):
        admm._rung_width = lambda S, bs: 0
    lp = problem(np.zeros_like(W), xbar, 0.0)
    _, f0 = admm.solve_batch_factored(*lp, settings=st)
    for width in WIDTHS + (S,):
        if width > S:
            continue
        cut = lambda a: a[:width]
        sub = tuple(cut(a) for a in lp)
        fsub = admm.Factors(*(cut(a) for a in f0))
        run = lambda: admm.solve_batch_frozen(*sub, fsub, settings=st)
        sol = run()
        ms = median_ms(run, args.reps)
        say(width=width, ms=ms, sweeps=int(sol.iters[0]),
            done=int(np.count_nonzero(sol.done)),
            us_per_sweep=1e3 * ms / max(int(sol.iters[0]), 1),
            **width_of(sol))
        if args.split and width in (256, S):
            say_split(width, op_split(run, args.out),
                      max(1, st.check_every))


def wheel(args, root):
    """One run of the benchmark's wheel cell with the megastep's fetched
    ``done_s`` read on its way through."""
    import contextlib
    import importlib

    import numpy as np

    sys.path.insert(0, root)
    with contextlib.redirect_stdout(sys.stderr):
        from benchmarks.harness import core
        from tpusppy import spopt

        cell = core.load_cell("farmer_cm4_s1000.wheel")
        conf, wl = cell["config_file"], cell["workload_file"]
        import jax

        from tpusppy.solvers import aot

        aot.arm_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        windows = []
        outcome = spopt.SPOpt._megastep_outcome

        def reading(self, meas, n_req):
            windows.append({
                "executed": int(meas["executed"]),
                "rows_done_last": int(np.count_nonzero(meas["done"])),
                "rows": int(meas["done"].size),
                "sweeps": [int(k) for k in
                           meas["iters"][:meas["executed"]]]})
            return outcome(self, meas, n_req)

        spopt.SPOpt._megastep_outcome = reading
        driver = importlib.import_module("benchmarks.drivers." + wl["driver"])
        obs = driver.run({
            "cell": "farmer_cm4_s1000.wheel", "config": conf, "workload": wl,
            "seed": args.seed, "data_seed": core.data_seed(args.seed),
            "seconds": 51.0, "trace": False, "t_start": T_START,
            "bench_dir": core.BENCH_DIR})
    done = [w["rows_done_last"] for w in windows]
    say(wheel="farmer_cm4_s1000.wheel", windows=len(windows),
        hub_iter_s=obs["end_to_end"]["hub_iter_s"],
        iterations=obs["iterations"],
        rows_done_last_median=statistics.median(done) if done else None,
        rows_done_last_min=min(done, default=None),
        rows_done_last_max=max(done, default=None))
    for w in windows:
        say(**w)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--root", default=HERE)
    ap.add_argument("--wheel", action="store_true")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--linger", action="store_true")
    ap.add_argument("--split", action="store_true")
    ap.add_argument("--reference", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--out", default=os.path.join(HERE, "chiprun_out"))
    ap.add_argument("--width", type=int, default=None)
    ap.add_argument("--scens", type=int, default=1000)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--refresh-every", type=int, default=16)
    ap.add_argument("--probe", type=int, nargs="*",
                    default=[3, 8, 16, 20, 32])
    args = ap.parse_args()
    root = os.path.abspath(args.root)
    sys.path.insert(0, root)
    if args.reference:
        return reference(args.reference, root)
    os.makedirs(args.out, exist_ok=True)
    if args.wheel:
        return wheel(args, root)
    return tables(args)


if __name__ == "__main__":
    main()
