"""Driver kind ``wheel``: one solo ``WheelSpinner`` for the whole window.

The deployment (configuration file) gives the model, its creator's
arguments, the scenario count, rho, the spokes and the solver recipe; the
workload file gives ``warmup_iterations`` (hub iterations that count as
set-up: the first ones compile), ``trace_seconds`` (how much of the window's
start a ``--trace 1`` run traces, at least), ``device_programs`` and the
checks.  The iteration limit
is out of reach and ``convthresh`` is -1, as under the server, so only
the harness's clock, or a certified gap, ends the wheel: the harness
plants the hub option ``preempt_check`` (the one the server's scheduler
plants) and asks the hub to park at the boundary that closes the window.
"""

from __future__ import annotations

import importlib

import numpy as np

from ..harness import core, observe, tracered

ITERATIONS_OUT_OF_REACH = 1_000_000


def build_wheel(conf, data_seed, watch):
    """(hub_dict, spokes, module, names, creator kwargs), through
    ``cfg_vanilla`` as ``examples/*/*_cylinders.py`` build them."""
    from tpusppy.utils import cfg_vanilla as vanilla
    from tpusppy.utils import config

    module = importlib.import_module("tpusppy.models." + conf["model"])
    S = int(conf["num_scens"])
    cfg = config.Config()
    cfg.num_scens_required()
    cfg.popular_args()
    cfg.two_sided_args()
    cfg.ph_args()
    for spoke in conf["spokes"]:
        getattr(cfg, spoke + "_args")()
    args = ["--num-scens", str(S),
            "--max-iterations", str(ITERATIONS_OUT_OF_REACH),
            "--default-rho", str(conf["default_rho"]),
            "--solver-options",
            " ".join(f"{k}={v}" for k, v in conf["solver_options"].items())]
    if conf.get("rel_gap") is not None:
        args += ["--rel-gap", str(conf["rel_gap"])]
    args += ["--" + spoke for spoke in conf["spokes"]]
    cfg.parse_command_line("benchmarks", args=args)
    kwargs = module.kw_creator(
        cfg, **dict(conf["creator_kwargs"], **{conf["seed_kwarg"]: data_seed}))
    names = module.scenario_names_creator(S)
    beans = dict(cfg=cfg, scenario_creator=module.scenario_creator,
                 scenario_denouement=getattr(module, "scenario_denouement",
                                             None),
                 all_scenario_names=names, scenario_creator_kwargs=kwargs)
    hub_dict = vanilla.ph_hub(**beans)
    spokes = [getattr(vanilla, spoke + "_spoke")(**beans)
              for spoke in conf["spokes"]]
    hub_dict["opt_class"] = observe.probed(hub_dict["opt_class"], watch)
    hub_dict["opt_kwargs"]["options"]["convthresh"] = -1.0
    hub_dict["hub_kwargs"]["options"]["preempt_check"] = watch.boundary
    return hub_dict, spokes, module, names, kwargs


def wheel_evidence(ref, watch, opt, outer, inner, incumbent, seed, **more):
    """What the wheel left behind, copied off the program's objects."""
    return dict(
        ref=ref, watch=watch, seed=seed, incumbent=incumbent,
        x=np.array(opt.local_x, dtype=float), W=np.array(opt.W, dtype=float),
        xbars=np.array(opt.xbars, dtype=float),
        rho=np.array(opt.rho, dtype=float),
        outer=float(outer), inner=float(inner),
        device_leaves=observe.device_state_leaves(opt), **more)


def run(ctx):
    from tpusppy.obs import metrics
    from tpusppy.spin_the_wheel import WheelSpinner

    conf, wl = ctx["config"], ctx["workload"]
    clock = observe.CompileClock()
    watch = observe.HubWatch(clock=core.now, annotate=ctx["trace"])
    rule = core.WindowRule(ctx["seconds"])
    st = {"tracer": None}

    def on_boundary(w, t, it):
        if rule.t0 is None:
            if it < wl["warmup_iterations"]:
                return False
            observe.hub_device_ready(w.opt)
            if ctx["trace"]:
                # the slice runs from this boundary to the first one at or
                # after trace_seconds that ends a hub step of the kind that
                # ended here (one iteration, or a megastep window), so that
                # it holds whole turns of the hub's cycle; a timer is the
                # fallback
                st["tracer"] = tracered.Tracer(2 * wl["trace_seconds"] + 5)
                st["tracer"].start()
                st["trace_from"] = (core.now(), w.step_iters > 1)
            st["registry"] = metrics.window().__enter__()
            st["compile0"] = clock.secs
            rule.open(core.now(), it)
            return False
        observe.hub_device_ready(w.opt)
        tr = st["tracer"]
        if (tr is not None and tr.running
                and core.now() - st["trace_from"][0] >= wl["trace_seconds"]
                and (w.step_iters > 1) == st["trace_from"][1]):
            tr.stop()
        if not rule.offer(core.now(), it):
            return False
        closed()
        return True

    def closed():
        st["counters"] = st["registry"].deltas()
        st["compile_s"] = clock.secs - st["compile0"]
        if st["tracer"] is not None:
            st["tracer"].stop()

    watch.on_boundary = on_boundary
    hub_dict, spokes, module, names, kwargs = build_wheel(
        conf, ctx["data_seed"], watch)
    ws = WheelSpinner(hub_dict, spokes)
    ws.spin()
    watch.done()
    hub, opt = ws.spcomm, ws.opt
    if rule.t1 is None:
        # the wheel ended of itself: a certified gap closes the window
        if rule.t0 is None:
            raise RuntimeError(
                "the wheel ended before the window opened "
                f"(iteration {hub.current_iteration()})")
        rule.close(core.now(), int(hub.current_iteration()))
        closed()
    peak = core.memory_peak_bytes()
    iters = rule.counted
    if iters <= 0:
        raise RuntimeError("no hub iteration completed inside the window")

    ref = core.load_reference(conf, ctx["bench_dir"])(module, names, kwargs)
    evidence = [wheel_evidence(ref, watch, opt, hub.BestOuterBound,
                               hub.BestInnerBound,
                               observe.incumbent_of(ws.spoke_comms),
                               ctx["seed"], first_iteration=rule.c0)]
    _abs_gap, rel_gap = hub.compute_gaps()
    return {
        "attempted": iters, "failed": 0,
        "end_to_end": {"hub_iter_s": rule.length / iters,
                       "setup_s": rule.t0 - ctx["t_start"]},
        "window_s": rule.length, "iterations": iters,
        "counters": st["counters"], "compile_s": st["compile_s"],
        "records": [], "requests": [],
        "host_rescued_iter0": int(watch.rescued0.sum()),
        "memory_peak_bytes": peak, "evidence": evidence,
        "tracer": st["tracer"],
        "notes": {"iterations": iters, "first_iteration": rule.c0,
                  "rel_gap": core.finite_or_none(rel_gap),
                  "outer": core.finite_or_none(evidence[0]["outer"]),
                  "inner": core.finite_or_none(evidence[0]["inner"]),
                  "lost_spokes": list(ws.lost_spokes),
                  "setup_compile_s": st["compile0"],
                  "compiles": clock.count, "cache_hits": clock.cache_hits},
    }
