"""UC benchmark: integer stochastic unit commitment at scale.

The reference's headline family is 1000-scenario stochastic UC with integer
commitment (paperruns/larger_uc/quartz/1000scen_fw:1-16, examples/uc/
uc_cylinders.py:74-80).  Two numbers:

- ``ph_iters_per_sec``: hub PH iteration rate over the S-scenario integer UC
  (LP-relaxed subproblems — exactly what the PH hub iterates on here), on the
  factorization-amortized sharded path.
- ``wall_s_to_gap``: wall-clock for a full in-process wheel (PH hub +
  Lagrangian outer bound + XhatShuffle integer-diving incumbents) to reach a
  certified MIP gap of ``BENCH_UC_GAP`` (default 1%).

``vs_baseline`` compares the PH iteration rate against the reference
architecture on this host: serial per-scenario HiGHS MIP solves.

Standalone: prints ONE JSON line.  Or imported by bench.py for the combined
line (`uc_metrics()`).
"""

import json
import os
import sys
import time

if os.environ.get("BENCH_TRACE"):
    import faulthandler
    faulthandler.dump_traceback_later(
        120, repeat=True, file=open("/tmp/bench_stacks.log", "w"))

import numpy as np


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def uc_metrics(progress=None, wheel=True):
    """UC metrics dict.  ``progress(partial_dict)`` (optional) is called
    with the rate-metric fields the moment they exist — BEFORE the
    long-running wheel — so a kill during the wheel still leaves the
    rate/MFU numbers in the artifact (bench.py relays them as a partial
    JSON line).  ``wheel=False`` skips the certified-gap wheel entirely
    (the ladder's rate-only smoke posture)."""
    import jax

    import tpusppy

    if not os.environ.get("BENCH_TRACE"):
        tpusppy.disable_tictoc_output()
    from tpusppy.ir import ScenarioBatch
    from tpusppy.parallel import sharded
    from tpusppy.solvers import flops as flops_model
    from tpusppy.solvers import scipy_backend
    from tpusppy.solvers import segmented as segmented_solvers
    from tpusppy.solvers.admm import ADMMSettings
    from tpusppy.solvers.sparse import SparseA

    # Default: the reference-shape scaled UC (30 gens x 24 h with min-up/
    # down, startup ramps, reserves — models/uc.py, shared-A engine),
    # matching examples/uc + paperruns/larger_uc in the reference.
    # BENCH_UC_MODEL=lite selects the small self-contained family.
    # Platform-matched defaults: the TPU run benches the reference's OWN
    # wind-ladder dataset when mounted (85-gen WECC-240; its LP relaxation
    # is ~0.07% tight, so 1% certification rides LP-quality bounds); the
    # CPU fallback degrades to the small self-contained family — the
    # 1-core fallback host cannot spin a 5-cylinder wheel on a 20+ gen
    # fleet inside the watchdog, and the artifact's job there is to prove
    # the certified pipeline end-to-end, flagged degraded.
    _wind_dir = os.environ.get(
        "BENCH_UC_DATA",
        "/root/reference/paperruns/larger_uc/1000scenarios_wind")
    platform = jax.devices()[0].platform
    if "BENCH_UC_MODEL" in os.environ:
        model_name = os.environ["BENCH_UC_MODEL"]
    elif platform == "cpu":
        model_name = "lite"
    elif os.path.isdir(_wind_dir):
        model_name = "data"
    else:
        model_name = "full"
    if model_name == "lite":
        from tpusppy.models import uc_lite as uc_model
        default_gens, default_horizon = 5, 12
    elif model_name == "data":
        # the reference's ACTUAL WECC-240 datasets (85 gens; demand
        # uncertainty in *scenarios_r1, wind ladders in paperruns) —
        # data-comparable benchmarking when the reference tree is mounted
        from tpusppy.models import uc_data as uc_model
        default_gens, default_horizon = 85, 48
    else:
        from tpusppy.models import uc as uc_model
        default_gens, default_horizon = 30, 24

    # explicit CPU run (JAX_PLATFORMS=cpu / BENCH_FORCE_CPU — bench.py never
    # picks the CPU itself): degrade scenario count AND problem shape so
    # the artifact lands within its timeout — flagged in the output
    # (degraded_cpu_run + the model name in the metric)
    degraded = platform == "cpu" and not os.environ.get("BENCH_UC_SCENS")
    S = int(os.environ.get("BENCH_UC_SCENS", "16" if degraded else "1000"))
    gens = int(os.environ.get(
        "BENCH_UC_GENS",
        str(min(5, default_gens) if degraded else default_gens)))
    horizon = int(os.environ.get(
        "BENCH_UC_HORIZON",
        str(min(12, default_horizon) if degraded
            else min(24, default_horizon))))
    # rate-metric iteration count: the real-data family runs ~40 s per PH
    # iteration at S=1000 (n=16008) — 8 iterations measure the steady rate
    # without blowing the parent's workload timeout
    iters = int(os.environ.get(
        "BENCH_UC_ITERS",
        "4" if degraded else ("8" if model_name == "data" else "30")))
    refresh_every = max(1, int(os.environ.get("BENCH_REFRESH", "16")))
    gap_target = float(os.environ.get("BENCH_UC_GAP", "0.01"))
    dtype = "float32" if platform != "cpu" else "float64"
    if dtype == "float64":
        jax.config.update("jax_enable_x64", True)
    eps = 1e-5 if dtype == "float32" else 1e-8
    # sweep_plateau: reference-scale UC batches park at a ~1e-1 worst /
    # 1e-2 median scaled residual regardless of budget (the frozen
    # 200-sweep loop never reaches eps and every extra sweep is waste);
    # the in-loop plateau exit stops the while_loop after 2 consecutive
    # non-improving windows.  The window ladder was measured end-to-end
    # on real WECC data (rate at S=1000 / wheel certification):
    #   w32: 0.124 it/s, 0.198% in 279.7 s   (med floor 8.0e-3)
    #   w16: 0.193 it/s, 0.198% in 233.6 s   (med floor 9.4e-3)
    #   w8:  0.316 it/s, 0.236% in 226.3 s   (med floor 1.4e-2)
    # Per-iteration PH progress (conv at a fixed iteration count) is
    # IDENTICAL across the ladder — the extra sweeps were pure waste —
    # and certification quality is unchanged vs the 1% target, so 8 is
    # the default; the artifact records the window used.
    # solve_refine=1: with the block/Woodbury structured KKT the x-update
    # preconditioner is built from EXACT small block inverses, and one
    # refinement pass holds the same residual floor as two (A/B at S=256:
    # identical median floor, 0.05% eobj drift, 1.22x faster sweeps);
    # refine=0 measurably corrupts the trajectory (16% eobj drift).
    plateau_window = int(os.environ.get("BENCH_PLATEAU_WINDOW", "8"))
    settings = ADMMSettings(
        dtype=dtype, eps_abs=eps, eps_rel=eps, max_iter=200, restarts=2,
        scaling_iters=6, polish_passes=1, solve_refine=1,
        sweep_plateau_rtol=0.05, sweep_plateau_window=plateau_window,
    )
    if os.environ.get("BENCH_PRECISION"):
        # operator-pinned frozen-sweep precision (the farmer bench's
        # autotuner sweeps it; the UC rate path takes the pin directly)
        import dataclasses
        settings = dataclasses.replace(
            settings, sweep_precision=os.environ["BENCH_PRECISION"])

    if model_name == "data":
        data_dir = _wind_dir
        if os.environ.get("BENCH_UC_GENS"):
            log("uc[data]: fleet comes from the dataset; "
                "BENCH_UC_GENS ignored (use BENCH_UC_HORIZON/SCENS)")
        names = uc_model.scenario_names_creator(data_dir=data_dir)
        if len(names) > S:
            names = names[:S]
        S = len(names)
        kw = {"data_dir": data_dir, "horizon": horizon,
              "relax_integers": False, "num_scens": S}
    else:
        kw = {"num_gens": gens, "horizon": horizon, "num_scens": S,
              "relax_integers": False}
        names = uc_model.scenario_names_creator(S)
    batch = ScenarioBatch.from_problems(
        [uc_model.scenario_creator(nm, **kw) for nm in names])
    log(f"uc[{model_name}] batch: {batch.num_scenarios} x "
        f"({batch.num_rows} rows, {batch.num_vars} vars, "
        f"{int(batch.is_int.sum())} ints, "
        f"shared_A={batch.A_shared is not None})")

    # ---- metric 1: hub PH iteration rate ---------------------------------
    from bench import _aot_segment_stats, _aot_stats_mark, _compile_span_secs
    from tpusppy.obs.sysmem import sample as _mem_sample

    from tpusppy import tune as tuner

    mesh = sharded.make_mesh()
    arr = sharded.shard_batch(batch, mesh)
    # AOT warm start (tpusppy/solvers/aot.py): SYNCHRONOUSLY deserialize
    # banked executables before anything compiles — the loader needs a
    # clean XLA state (see tune.prewarm_aot), so no overlap by design
    t_seg = time.perf_counter()
    aot_base = _aot_stats_mark()
    tuner.prewarm_aot()
    refresh, frozen = sharded.make_ph_step_pair(
        batch.tree.nonant_indices, settings, mesh)
    state = sharded.init_state(arr, 1.0, settings)
    from tpusppy.obs import trace as obs_trace

    t0 = time.time()
    with obs_trace.span("compile", "compile.iter0"):
        state, out, _ = refresh(state, arr, 0.0)
        np.asarray(out.conv)
    compile_iter0_s = time.time() - t0
    log(f"uc compile+iter0: {compile_iter0_s:.1f}s "
        f"eobj={float(np.asarray(out.eobj)):.2f}")
    t0 = time.time()
    with obs_trace.span("compile", "compile.steps"):
        state, out, factors = refresh(state, arr, 1.0)
        state, out = frozen(state, arr, 1.0, factors)
        np.asarray(out.conv)
    t_first_dispatch = time.time() - t0

    t0 = time.time()
    for i in range(iters):
        if i % refresh_every == 0:
            state, out, factors = refresh(state, arr, 1.0)
        else:
            state, out = frozen(state, arr, 1.0, factors)
    conv = float(np.asarray(out.conv))
    iters_per_sec = iters / (time.time() - t0)
    sweeps = float(np.asarray(out.iters))
    log(f"uc PH: {iters_per_sec:.3f} iters/sec (conv={conv:.3e}, "
        f"sweeps/iter={sweeps:.0f})")

    # FLOP-model MFU for the UC rate segment (solvers/flops.py): shared-A
    # engine => one factorization per refresh; the SparseA engine's model
    # flops are the dense accounting scaled by the same measured factor
    # the dispatch model uses
    sparse_f = (segmented_solvers.SPARSE_DISPATCH_FACTOR
                if isinstance(arr.A, SparseA) else 1.0)
    flops_it = flops_model.ph_iteration_flops(
        batch.num_scenarios, batch.num_vars, batch.num_rows, sweeps,
        refresh_every, settings.restarts, factor_batch=1,
        sparse_factor=sparse_f)
    mfu, mfu_note = flops_model.mfu_pct(
        iters_per_sec, flops_it, len(mesh.devices.flat), jax.devices()[0],
        settings.sweep_mode())

    # FULL-reference-horizon submetric (horizon 48, n=32016 at S=1000):
    # the shape the dense engine could never fit on one chip (4.1 GB
    # Kinv + 3.2 GB dense A); the sparse/block-Woodbury engine runs it —
    # record the rate as capability evidence.  TPU real-data runs only.
    h48_rate = None
    if (model_name == "data" and platform != "cpu"
            and horizon < 48 and not os.environ.get("BENCH_UC_NO_H48")):
        try:
            kw48 = dict(kw, horizon=48)
            b48 = ScenarioBatch.from_problems(
                [uc_model.scenario_creator(nm, **kw48) for nm in names])
            arr48 = sharded.shard_batch(b48, mesh)
            r48, f48 = sharded.make_ph_step_pair(
                b48.tree.nonant_indices, settings, mesh)
            st48 = sharded.init_state(arr48, 1.0, settings)
            st48, o48, _ = r48(st48, arr48, 0.0)
            np.asarray(o48.conv)
            st48, o48, fac48 = r48(st48, arr48, 1.0)
            np.asarray(o48.conv)
            t0 = time.time()
            n48 = 3
            for _ in range(n48):
                st48, o48 = f48(st48, arr48, 1.0, fac48)
            np.asarray(o48.conv)
            h48_rate = n48 / (time.time() - t0)
            log(f"uc h48 (n={b48.num_vars}): {h48_rate:.4f} iters/sec")
            del arr48, st48, o48, fac48, r48, f48, b48
        except Exception as e:          # capability metric is additive
            log(f"uc h48 probe failed: {e!r}")

    # baseline: serial per-scenario HiGHS MIP loop (reference architecture),
    # sampled ADAPTIVELY — reference-scale UC MIPs cost tens of seconds each
    # on this host, so the sample stops once ~90s of baseline evidence is
    # in.  The cap is 24 (not 8): per-scenario MIP difficulty varies ~2x
    # across the wind scenarios and an 8-sample mean wobbled the headline
    # ratio run-to-run; more samples inside the same budget tighten it
    sample_cap = min(24, S)
    budget_s = float(os.environ.get("BENCH_UC_BASELINE_BUDGET", "90"))
    t0 = time.time()
    sample = 0
    for s in range(sample_cap):
        scipy_backend.solve_lp(
            batch.c[s], batch.A[s], batch.cl[s], batch.cu[s],
            batch.lb[s], batch.ub[s], is_int=batch.is_int,
            mip_rel_gap=1e-4, time_limit=60,
        )
        sample += 1
        if time.time() - t0 > budget_s:
            break
    from bench import RANKS
    t_mip = (time.time() - t0) / sample
    base_ips = 1.0 / (t_mip * S)
    base32 = base_ips * RANKS  # IDEAL rank scaling (BASELINE.md accounting)
    log(f"uc baseline (serial HiGHS MIP): {t_mip*1e3:.1f} ms/scenario "
        f"=> {base_ips:.4f} iters/sec serial, {base32:.4f} at ideal "
        f"{RANKS}-rank scaling")

    # compile_s: the trace-ring compile spans when the recorder is on
    # (exact — aot.compile/aot.load time nothing but the compile work),
    # else the first-dispatch heuristic, labeled either way (bench.py's
    # _compile_span_secs; the negative-clamp satellite fix)
    compile_span = _compile_span_secs(t_seg)
    if compile_span is not None:
        compile_s, compile_estimator = compile_span, "trace_spans"
    else:
        compile_s = max(0.0, t_first_dispatch
                        - 2.0 / max(iters_per_sec, 1e-9))
        compile_estimator = "dispatch_heuristic"
    rate_fields = {
        "model": model_name,
        "ph_iters_per_sec": round(iters_per_sec, 4),
        # cold-start observability (ROADMAP item 3): explicit compile-
        # span seconds when traced, the first-dispatch heuristic
        # otherwise, plus the raw compile+iter0 wall the r5 artifacts
        # quote (~17s UC) and the executable-cache evidence
        "compile_s": round(compile_s, 2),
        "compile_s_estimator": compile_estimator,
        "compile_iter0_s": round(compile_iter0_s, 2),
        "aot": _aot_segment_stats(aot_base),
        "precision": settings.sweep_mode(),
        "plateau_window": plateau_window,
        "sweeps_per_iter": round(sweeps, 1),
        "mfu_pct": round(mfu, 2) if mfu is not None else None,
        "mfu_note": mfu_note,
        "h48_ph_iters_per_sec": (round(h48_rate, 4) if h48_rate else None),
        "vs_baseline": round(iters_per_sec / base_ips, 2),
        "vs_baseline_32rank": round(iters_per_sec / base32, 2),
        "S": S, "degraded_cpu_run": degraded,
        # memory watermarks (tpusppy.obs.sysmem; doc/scaling.md): host
        # peak RSS is a process high-water mark, device peak reads 0 on
        # XLA:CPU (no backend memory stats)
        **_mem_sample(),
    }
    if progress is not None:
        # bank the rate/MFU segment NOW: the wheel below can run for
        # thousands of seconds and a kill there must not lose these
        progress(dict(rate_fields, wall_s_to_gap=None, gap_pct=None,
                      gap_target_pct=gap_target * 100, certified=False,
                      wheel_pending=True))
    if not wheel:
        return dict(rate_fields, wall_s_to_gap=None, gap_pct=None,
                    gap_target_pct=gap_target * 100, certified=False,
                    wheel_skipped=True)

    # free the rate-metric's device residency before the wheel: the S=1000
    # arrays + factors (~6 GB at reference shape) plus the compiled S=1000
    # executables (~0.5 GB code each) otherwise coexist with the wheel's
    # per-cylinder factors and OOM the chip
    del arr, state, out, factors, refresh, frozen
    import gc

    from tpusppy import spopt as _spopt
    _spopt.clear_device_caches()
    gc.collect()
    jax.clear_caches()

    # ---- metric 2: wall-clock to certified MIP gap (full wheel) ----------
    from tpusppy.cylinders import (
        LagrangianOuterBound, PHHub, SlamMaxHeuristic, XhatRestrictedEF,
        XhatShuffleInnerBound, XhatXbarInnerBound)
    from tpusppy.opt.ph import PH
    from tpusppy.phbase import PHBase
    from tpusppy.spin_the_wheel import WheelSpinner
    from tpusppy.xhat_eval import Xhat_Eval

    # FULL-SCALE wheel by default (r5): the donor-dual outer bound,
    # repair-based certified evaluation, shared batch cache and the
    # trimmed full-scale cylinder set certify the complete 1000-scenario
    # reference UC on one chip (r5 runs: 0.56% <= 1% in ~1725 s to gap).
    # The artifact reports wheel_S honestly either way.
    S_wheel = min(S, int(os.environ.get(
        "BENCH_UC_WHEEL_SCENS", str(S) if degraded else "1000")))
    if S_wheel != S:
        names = names[:S_wheel]
        kw = dict(kw, num_scens=S_wheel)

    # trimmed adaptive budget: UC prox/LP batches plateau around 1e-3
    # primal regardless of sweeps, so a deep budget only burns time — the
    # rescue-tolerance ladder + host rescue covers the tail, and frozen
    # iterations accept at the ladder (spopt._solve_amortized).  The
    # non-degraded (TPU) wheel runs the budget the S=64 certification was
    # validated with.
    if degraded:
        so = {"dtype": dtype, "eps_abs": eps, "eps_rel": eps,
              "max_iter": 300, "restarts": 3, "scaling_iters": 10,
              "polish_passes": 1}
    else:
        so = {"dtype": dtype, "eps_abs": eps, "eps_rel": eps,
              "max_iter": 100, "restarts": 2, "scaling_iters": 6,
              "polish_passes": 1, "solve_refine": 1,
              "sweep_plateau_rtol": 0.05,
              "sweep_plateau_window": plateau_window}

    # host-MILP budgets scale with problem size: the degraded CPU shape
    # solves scenario MIPs in ~0.5-2 s (full lifts + dual ascent are
    # affordable); the reference 30x24 shape costs 20-120 s per MIP, so
    # lifts are partial there (still certified — any completed subset is)
    lift_budget = float(os.environ.get("BENCH_UC_LIFT_S",
                                       "45" if degraded else "120"))
    ascent_budget = float(os.environ.get("BENCH_UC_ASCENT_S",
                                         "90" if degraded else "120"))
    # full-S wheel (wheel_S == S == 1000): everything is ~15x the S=64
    # device work on the same single chip + single host core, so the
    # budget goes to what certification actually needs — the real
    # WECC-240 LP relaxation is 0.07-0.12% tight, so LP-dual Lagrangian
    # bounds (lift every 4th pass, not every pass) + ONE good incumbent
    # close 1% without the per-iteration MILP machinery
    full_scale = S_wheel >= 512
    lift_every = int(os.environ.get("BENCH_UC_LIFT_EVERY",
                                    "4" if full_scale else "1"))
    if full_scale:
        lift_budget = float(os.environ.get("BENCH_UC_LIFT_S", "60"))
    # inner-bound cylinders: with the model repair (uc_data.repair_fn) the
    # certified incumbent quality IS the eval solve quality (repair prices
    # the leftover slack at VOLL) — deeper budget, no plateau shortcuts
    # (measured at the fixture shape: 200/2 -> +4.7% over exact, 1000/4 ->
    # +0.07%).  The dict is reused by the spoke configs below.
    so_eval = dict(so, max_iter=1000, restarts=4, sweep_plateau_rtol=0.0)

    trace_prefix = os.environ.get("BENCH_UC_TRACE_PREFIX")

    def okw(iters=60):
        return {
            # one 1000-scenario batch build costs minutes of the 1-core
            # host; all cylinders share it (read-only by contract)
            "options": {"batch_cache": True,
                        **({"trace_prefix": trace_prefix}
                           if trace_prefix else {}),
                        "defaultPHrho": 500.0, "PHIterLimit": iters,
                        "convthresh": -1.0, "xhat_dive_rounds": 16,
                        "solver_options": so,
                        "xhat_looper_options": {"scen_limit": 3},
                        "xhat_xbar_options": {
                            "thresholds": [0.5, 0.4, 0.35, 0.3, 0.25]
                            if degraded else [0.5, 0.35]},
                        # every=2, NOT 1 (A/B'd at full scale): every=1
                        # lands the FIRST restricted-EF candidate one hub
                        # iteration earlier but it is a WORSE incumbent —
                        # the wheel certified 0.899% (thin margin) vs the
                        # 0.34% the one-iteration-later consensus gives,
                        # with no wall-clock win on an idle host
                        "xhat_ef_options": {"every": 2, "ksub": 6,
                                            "time_limit": 120.0},
                        "lagrangian_milp_lift": {"budget_s": lift_budget,
                                                 "every": lift_every,
                                                 "mip_rel_gap": 1e-4,
                                                 "time_limit": 30.0},
                        # full scale: exact donor duals transferred
                        # batch-wide (spopt.dual_donor_bounds) — the
                        # certified outer bound no longer rides S=1000
                        # plateaued ADMM duals
                        **({"lagrangian_dual_donors": {
                            "k": 24, "budget_s": 120.0,
                            "time_limit": 20.0},
                            # the S=1000 batched solve starves the wheel
                            # and its plateaued duals lose to donors
                            # anyway — donors ARE the outer bound here
                            "lagrangian_skip_solve": True}
                           if full_scale else {}),
                        # full scale: no subgradient ascent at teardown —
                        # each of its steps is a batched S-solve (the exact
                        # cost lagrangian_skip_solve removes), and the
                        # donor pass at the final W is the polish
                        **({} if full_scale else {
                            "lagrangian_milp_ascent": {
                                "steps": 10, "budget_s": ascent_budget,
                                "mip_rel_gap": 1e-3, "time_limit": 30.0,
                                "skip_if_gap_at": gap_target}})},
            "all_scenario_names": names,
            "scenario_creator": uc_model.scenario_creator,
            "scenario_creator_kwargs": kw,
        }

    hub_iters = int(os.environ.get(
        "BENCH_UC_PH_ITERS", "16" if full_scale else "40"))
    # resilience (tpusppy.resilience): with BENCH_UC_CKPT_DIR set (the
    # ladder's --resume path wires it per rung) the wheel checkpoints
    # asynchronously and a re-run warm-starts from the newest snapshot —
    # a SIGKILLed rung loses at most one checkpoint cadence, not the rung
    hub_opts = {"rel_gap": gap_target}
    wheel_resume = None
    ckpt_dir = os.environ.get("BENCH_UC_CKPT_DIR")
    if ckpt_dir:
        hub_opts.update(
            checkpoint_dir=ckpt_dir,
            checkpoint_every_secs=float(
                os.environ.get("BENCH_UC_CKPT_SECS", "60")),
            checkpoint_every_iters=int(
                os.environ.get("BENCH_UC_CKPT_ITERS", "0")) or None)
        # resuming is EXPLICIT (BENCH_UC_RESUME, set by bench.py's
        # --resume): a stale checkpoint must never silently warm-start a
        # run that claims to be a cold measurement
        if os.environ.get("BENCH_UC_RESUME") == "1":
            from tpusppy.resilience import checkpoint as _ckpt
            if _ckpt.latest(ckpt_dir) is not None:
                wheel_resume = ckpt_dir
                log(f"uc wheel: resuming from checkpoint dir {ckpt_dir}")
    hub_dict = {
        "hub_class": PHHub,
        "hub_kwargs": {"options": hub_opts},
        "opt_class": PH,
        "opt_kwargs": okw(hub_iters),
    }
    def okw_eval(**extra):
        o = okw()
        o["options"] = dict(o["options"], solver_options=so_eval, **extra)
        return o

    spokes = [
        {"spoke_class": LagrangianOuterBound, "opt_class": PHBase,
         "opt_kwargs": okw()},
        {"spoke_class": XhatRestrictedEF, "opt_class": Xhat_Eval,
         "opt_kwargs": okw_eval()},
        # donor-MILP shuffle: exact scenario-MIP first stages as candidates
        # (the reference's donor semantics) — lands integer-feasible
        # incumbents within the first hub iterations instead of waiting for
        # consensus to crystallize for the restricted EF
        {"spoke_class": XhatShuffleInnerBound, "opt_class": Xhat_Eval,
         "opt_kwargs": okw_eval(
             xhat_looper_options={"scen_limit": 2, "donor_milp": True,
                                  "donor_milp_time": 60.0})},
    ]
    if not full_scale:
        # the threshold-ladder xbar evaluator earns its keep at S=64 but
        # each ladder entry costs a full cold S-batch solve: at S=1000 it
        # starves the chip (and its candidates carry plateaued LP
        # scenarios — the restricted EF is what lands incumbents there)
        spokes.insert(1, {"spoke_class": XhatXbarInnerBound,
                          "opt_class": Xhat_Eval, "opt_kwargs": okw_eval()})
    if degraded:
        # the small CPU family benefits from donor cycling + slam too
        spokes += [
            {"spoke_class": XhatShuffleInnerBound, "opt_class": Xhat_Eval,
             "opt_kwargs": okw()},
            {"spoke_class": SlamMaxHeuristic, "opt_class": Xhat_Eval,
             "opt_kwargs": okw()},
        ]
    # watchdog: the wheel must never block the bench line (daemon thread +
    # bounded join; on timeout the farmer metric still prints)
    import threading

    # measured on chip: the real-data S=64 wheel certifies ~0.15% around
    # 610-1370 s (in-wheel compiles + when the restricted-EF incumbent
    # lands are both high-variance), so the watchdog stretches to whatever
    # budget remains before the parent's deadline (minus teardown margin)
    # rather than a fixed number.
    explicit = "BENCH_UC_WHEEL_TIMEOUT" in os.environ
    budget = float(os.environ.get("BENCH_UC_WHEEL_TIMEOUT", "1500"))
    deadline = float(os.environ.get("BENCH_CHILD_DEADLINE", "0") or 0)
    if deadline:
        # grow OR shrink to what actually remains (the parent SIGKILLs the
        # child at its deadline, losing the whole JSON line); an explicit
        # BENCH_UC_WHEEL_TIMEOUT is only ever shrunk, never overridden up
        remaining = max(600.0, deadline - time.time() - 300.0)
        budget = min(budget, remaining) if explicit else remaining
        log(f"uc wheel watchdog: {budget:.0f}s (deadline-derived)")
    result = {}

    def _spin():
        t0 = time.time()
        try:
            ws = WheelSpinner(hub_dict, spokes, resume=wheel_resume).spin()
        except Exception as e:       # error != timeout; surface which
            result["error"] = repr(e)
            return
        total = time.time() - t0
        # wall to the hub's gap-based termination (construction + hub
        # loop); the extra teardown minutes (final spoke passes) are
        # reported separately as wall_s_total
        result["wall"] = float(getattr(ws, "gap_wall_secs", total))
        result["wall_total"] = total
        result["ib"] = ws.BestInnerBound
        result["ob"] = ws.BestOuterBound

    th = threading.Thread(target=_spin, daemon=True)
    th.start()
    th.join(timeout=budget)
    if "wall" not in result:
        why = result.get("error", f"timeout after {budget:.0f}s")
        log(f"uc wheel: {why}")
        out = dict(
            rate_fields, wheel_S=S_wheel,
            wall_s_to_gap=None, gap_pct=None,
            gap_target_pct=gap_target * 100, certified=False,
        )
        if "error" in result:
            out["wheel_error"] = result["error"]
        else:
            out["wheel_timeout_s"] = budget
        return out
    wall, ib, ob = result["wall"], result["ib"], result["ob"]
    wall_total = result.get("wall_total", wall)
    gap = (ib - ob) / max(abs(ib), 1e-9) if np.isfinite(ib) else float("inf")
    # sanity: certified bounds can cross only by tolerance dust; a materially
    # negative gap means an INVALID bound slipped in — never report it as a
    # certification (this caught the primal trivial-bound bug in r5)
    crossed = np.isfinite(gap) and gap < -1e-6
    log(f"uc wheel: {wall:.1f}s inner={ib:.2f} outer={ob:.2f} "
        f"gap={gap*100:.2f}%" + (" CROSSED-BOUNDS" if crossed else ""))

    return dict(
        rate_fields, wheel_S=S_wheel,
        wall_s_to_gap=round(wall, 1),
        wall_s_total=round(wall_total, 1),
        gap_pct=round(gap * 100, 3),
        gap_target_pct=gap_target * 100,
        certified=bool(np.isfinite(ib) and np.isfinite(ob)
                       and not crossed and gap <= gap_target + 1e-9),
        **({"crossed_bounds": True} if crossed else {}),
        **_mem_sample(),        # wheel-phase memory high-water
    )


def main():
    m = uc_metrics()
    print(json.dumps({
        "metric": f"ph_iters_per_sec_uc{m['S']}",
        "value": m["ph_iters_per_sec"],
        "unit": "iter/s",
        "vs_baseline": m["vs_baseline"],
        "uc": m,
    }))
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(0)      # see bench.py: daemon wheel threads abort teardown


if __name__ == "__main__":
    main()
