"""Driver kind ``serve_closed``: an in-process ``SolveServer`` with every
default it has, and ``clients`` closed-loop clients (each sends its next
request when the last one's result is back).

Every request names the deployment's model, scenario count and creator
arguments with a ``seedoffset`` of its own drawn from ``--seed``, the
configuration's ``PHIterLimit`` and solver recipe, and what the workload
file's ``request_options`` add (nothing, in a cell that runs the server's
defaults): one family, so every measured request is a warm hit.  Set-up sends one whole
request of that family (``linger_secs: 0``, which the server keeps out of
the family key) and waits for it.  The generator stops submitting at
``--seconds``; the window closes when the requests then in flight are
back.  The clients are threads of this process and do nothing but wait.
"""

from __future__ import annotations

import importlib
import shutil
import threading

import numpy as np

from ..harness import core, observe, tracered
from .wheel import wheel_evidence


def make_server(watches, annotate):
    from tpusppy.service.server import SolveServer

    class ProbedServer(SolveServer):
        """The server with its hub observed: Iter0 through the subclass
        seam, the boundaries in front of the scheduler's own
        ``preempt_check``.  Scheduler, wheel and caches are untouched."""

        def _build_wheel(self, t, preempt_check, on_iter0_done):
            watch = observe.HubWatch(clock=core.now, annotate=annotate)
            watches.setdefault(t.id, []).append(watch)

            def check():
                watch.boundary()
                return preempt_check()

            hub_dict, spokes = super()._build_wheel(t, check, on_iter0_done)
            hub_dict["opt_class"] = observe.probed(hub_dict["opt_class"],
                                                   watch)
            for sd in spokes:
                sd["spoke_class"] = observe.probed_spoke(sd["spoke_class"],
                                                         watch)
            return hub_dict, spokes

    return ProbedServer()


def request_seed(seed, k):
    """The k-th request's data seed: distinct for every (seed, k)."""
    rng = np.random.default_rng([int(seed), int(k)])
    return int(rng.integers(0, 2_000_000_000))


def run(ctx):
    import jax

    from tpusppy.obs import metrics
    from tpusppy.service.server import SolveRequest

    conf, wl = ctx["config"], ctx["workload"]
    clock = observe.CompileClock()
    watches = {}
    iter_limit = int(conf["max_iterations"])
    clients = int(wl["clients"])

    def request(k, **options):
        kw = dict(conf["creator_kwargs"],
                  **{conf["seed_kwarg"]: request_seed(ctx["seed"], k)})
        return kw, SolveRequest(
            model=conf["model"], num_scens=int(conf["num_scens"]),
            creator_kwargs=kw,
            options=dict({"PHIterLimit": iter_limit,
                          "solver_options": dict(conf["solver_options"])},
                         **options))

    server = make_server(watches, ctx["trace"])
    done = []                      # one dict per measured request
    lock = threading.Lock()
    try:
        # set-up: one whole request of the family, not measured
        _kw, req = request(0, linger_secs=0.0)
        warm = server.result(server.submit(req), timeout=1100.0)
        if warm["status"] != "done":
            raise RuntimeError(f"the warm-up request ended {warm['status']}: "
                               f"{warm.get('error')}")
        tracer = None
        if ctx["trace"]:
            tracer = tracered.Tracer(wl["trace_seconds"])
            tracer.start()
        registry = metrics.window().__enter__()
        compile0 = clock.secs
        rule = core.WindowRule(ctx["seconds"])
        rule.open(core.now(), 0)
        counter = iter(range(1, 1 << 30))

        def client():
            while core.now() - rule.t0 < rule.seconds:
                with lock:
                    k = next(counter)
                kw, req = request(k, **wl.get("request_options", {}))
                with jax.profiler.TraceAnnotation("bench:request"):
                    t_submit = core.now()
                    rid = server.submit(req)
                    rec = server.result(rid, timeout=1100.0)
                    t_done = core.now()
                with lock:
                    done.append({"k": k, "kw": kw, "rid": rid, "record": rec,
                                 "t_submit": t_submit, "t_done": t_done})

        threads = [threading.Thread(target=client, name=f"client{i}")
                   for i in range(clients)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        rule.close(max(r["t_done"] for r in done), len(done))
        counters = registry.deltas()
        compile_s = clock.secs - compile0
        if tracer is not None:
            tracer.stop()
    finally:
        server.shutdown(wait=False, timeout=60.0)
        shutil.rmtree(server.work_dir, ignore_errors=True)
    peak = core.memory_peak_bytes()

    module = importlib.import_module("tpusppy.models." + conf["model"])
    names = module.scenario_names_creator(int(conf["num_scens"]))
    reference = core.load_reference(conf, ctx["bench_dir"])
    requests, evidence, failed = [], [], 0
    for r in sorted(done, key=lambda r: r["t_submit"]):
        rec, ws = r["record"], watches.get(r["rid"], [])
        if rec["status"] != "done" or not ws or ws[-1].opt is None:
            failed += 1
            continue
        w = ws[-1]
        first = ws[0]
        t_last = w.t_first_at(int(rec["iters"]))
        requests.append({
            "request_s": r["t_done"] - r["t_submit"],
            "client_ttfi_s": first.t_iter0 - r["t_submit"],
            "post_iter_s": r["t_done"] - t_last,
            "exec_iter_s": (t_last - first.t_iter0) / max(1, rec["iters"]),
            "iters": int(rec["iters"]),
        })
        # the reference's data comes from the creator, not from what the
        # server ingested: ingest is under test too
        kwargs = module.kw_creator(**dict(r["kw"],
                                          num_scens=int(conf["num_scens"])))
        evidence.append(wheel_evidence(
            reference(module, names, kwargs), w, w.opt,
            rec["outer"], rec["inner"],
            observe.incumbent_of([c for sl in ws for c in sl.spokes]),
            ctx["seed"], record=rec, iter_limit=iter_limit))
    if not requests:
        raise RuntimeError("no request completed inside the window")
    n = len(requests)
    return {
        "attempted": len(done), "failed": failed,
        "end_to_end": {
            "request_s": sum(r["request_s"] for r in requests) / n,
            "ttfi_s": sum(r["client_ttfi_s"] for r in requests) / n,
            "request_rate": n / rule.length,
            "setup_s": rule.t0 - ctx["t_start"]},
        "window_s": rule.length, "iterations": sum(r["iters"] for r in requests),
        "counters": counters, "compile_s": compile_s,
        "records": [r["record"] for r in done], "requests": requests,
        "memory_peak_bytes": peak, "evidence": evidence, "tracer": tracer,
        "notes": {"requests": n,
                  "warm_hits": sum(bool(r["record"]["warm_hit"]) for r in done),
                  "certified": sum(bool(r["record"]["certified"])
                                   for r in done)},
    }

