"""WheelSpinner: launch a hub and its spokes and spin until termination.

TPU-native analogue of ``mpisppy/spin_the_wheel.py:12-237``.  The reference
splits ``COMM_WORLD`` into strata/cylinder process groups and runs one opt
object per rank (spin_the_wheel.py:219-237).  Here each cylinder is a host
thread driving its own jitted device programs (batched solves share the device
through the run queue — algorithm parallelism P3 of SURVEY §2.12), and the
cross-cylinder fabric is the write-id versioned mailbox set
(:mod:`tpusppy.cylinders.spcommunicator`).

Call sequence mirrors the reference: construct opt + communicator per cylinder,
make windows, ``setup_hub``, run all mains, hub sends the kill sentinel,
spokes finalize, hub_finalize (spin_the_wheel.py:119-144).
"""

from __future__ import annotations

import csv
import os
import threading
import time

import numpy as np

from . import global_toc
from .cylinders.spcommunicator import WindowFabric
from .obs import trace as _trace
from .solvers import turns as _turns


class WheelSpinner:
    """Spin a hub and list of spokes (spin_the_wheel.py:12-159).

    Resilience (tpusppy.resilience, doc/resilience.md): the hub options
    may carry ``checkpoint_dir`` (+ ``checkpoint_every_secs`` /
    ``checkpoint_every_iters`` / ``checkpoint_keep``) to snapshot the
    wheel asynchronously, ``resume`` (or the ``resume=`` ctor arg) to
    warm-start from the newest checkpoint, ``spoke_timeout_secs`` to
    declare a progress-less spoke wedged, and ``strict_spokes`` to
    restore the legacy raise-on-spoke-crash teardown.  By default a
    crashed spoke is marked LOST (``self.lost_spokes``) and the wheel
    completes with whatever the remaining bounders certified.
    """

    def __init__(self, hub_dict, list_of_spoke_dict, resume=None):
        self.hub_dict = dict(hub_dict)
        self.list_of_spoke_dict = [dict(d) for d in (list_of_spoke_dict or [])]
        self.on_hub = True  # single-process: we always see the hub
        self.spun = False
        self.resume = resume
        self.lost_spokes = []
        self.spoke_errors = []
        self.resumed_from = None

    def spin(self, comm_world=None):
        """comm_world accepted for reference API parity; unused in-process."""
        return self.run()

    def _hub_options(self) -> dict:
        return dict(self.hub_dict.get("hub_kwargs", {}).get("options") or {})

    def _load_resume(self):
        """The checkpoint to warm-start from (ctor arg wins over the hub
        option); None means cold start — including a --resume pointed at
        a dir that has no checkpoint yet (first run of a retried job)."""
        from .resilience import checkpoint as _ckpt

        src = self.resume or self._hub_options().get("resume")
        if not src:
            return None
        ck = _ckpt.load_latest(src)
        if ck is None:
            global_toc(f"resume: no checkpoint under {src!r} — cold start",
                       True)
        return ck

    def _make_checkpointer(self, fresh_start: bool = False):
        opts = self._hub_options()
        if not opts.get("checkpoint_dir"):
            return None
        from .resilience.checkpoint import CheckpointManager

        return CheckpointManager(
            opts["checkpoint_dir"],
            every_secs=opts.get("checkpoint_every_secs", 60.0),
            every_iters=opts.get("checkpoint_every_iters"),
            keep=opts.get("checkpoint_keep", 3),
            fresh_start=fresh_start)

    def _wire_resilience(self, hub_comm, hub_opt):
        """Shared resume + checkpointer hookup for both spinner variants
        (call after ``setup_hub``).  Returns the CheckpointManager (or
        None).  Bounds always re-seed; the PH-state restore is consumed
        by ``PHBase.Iter0`` — opt classes that never run it (APH's own
        driver) get a bounds-only resume, reported by
        :meth:`_warn_unconsumed_resume` at teardown."""
        ckpt = self._load_resume()
        if ckpt is not None:
            hub_opt._resume_ckpt = ckpt
            hub_comm.seed_resume(ckpt)
            self.resumed_from = ckpt.iteration
        mgr = self._make_checkpointer(fresh_start=ckpt is None)
        if mgr is not None:
            hub_comm.attach_checkpointer(mgr)
        self._prewarm_executables(ckpt)
        return mgr

    def _prewarm_executables(self, ckpt):
        """Warm start for the COMPILES, not just the math: arm the AOT
        executable cache from a resume checkpoint's carried pointer
        (checkpoint + cache compose — the resumed process reaches its
        first PH iteration warm even when its own env never named the
        cache), then deserialize the cached programs NOW, before the
        cylinder threads start: this jaxlib's executable loader races
        in-flight XLA compiles (see tpusppy/solvers/aot.py), so the bulk
        load must happen while this thread is the only one touching the
        backend."""
        from .solvers import aot as _aot

        if ckpt is not None and not _aot.cache_path():
            src = (ckpt.meta or {}).get("aot_cache")
            if src and os.path.isdir(src):
                _aot.set_cache_path(src)
                global_toc(
                    f"resume: AOT executable cache armed from the "
                    f"checkpoint pointer ({src})", True)
        if _aot.enabled():
            n = _aot.prewarm()
            if n:
                global_toc(f"AOT cache: {n} executable(s) prewarmed", True)

    @staticmethod
    def _warn_unconsumed_resume(hub_opt):
        """A resume checkpoint nobody consumed means the opt class never
        ran the PHBase.Iter0 restore seam (e.g. APH's own driver): the
        run still got the re-seeded bounds, but W/rho restarted cold and
        the iteration count did NOT continue — say so instead of letting
        ``resumed_from`` imply a full warm start."""
        if getattr(hub_opt, "_resume_ckpt", None) is not None:
            hub_opt._resume_ckpt = None
            global_toc(
                f"WARNING: resume checkpoint was NOT consumed by "
                f"{type(hub_opt).__name__} (no PHBase.Iter0 in its "
                "driver): bounds were re-seeded but PH state restarted "
                "cold and PHIterLimit did not continue from the "
                "snapshot", True)

    def _final_checkpoint(self, hub_comm, mgr):
        """Bank the terminal state (post bound-harvest) and drain the
        writer: a later ``--resume`` of a COMPLETED run then reloads the
        certified end state instead of re-running the wheel."""
        if mgr is None:
            return
        from .resilience import checkpoint as _ckpt

        try:
            mgr.capture(hub_comm.current_iteration(),
                        lambda: _ckpt.capture_ph(hub_comm.opt, hub=hub_comm))
        except Exception as e:     # capture must never cost the results
            from .obs import metrics as _metrics

            _metrics.inc("checkpoint.capture_errors")
            global_toc(f"WARNING: final checkpoint capture failed: {e!r}",
                       True)
        mgr.close()

    @staticmethod
    def _cylinder_opt_kwargs(opt_kwargs):
        """Wheel-context solver defaults: several cylinders' factors coexist
        on one chip, so shared-A factors drop the exact K and refine
        matrix-free (factors_keep_K) unless the caller pinned it.
        Deep-copies only the dicts it touches."""
        opt_kwargs = dict(opt_kwargs)
        options = dict(opt_kwargs.get("options") or {})
        so = dict(options.get("solver_options") or {})
        so.setdefault("factors_keep_K", False)
        options["solver_options"] = so
        opt_kwargs["options"] = options
        return opt_kwargs

    def run(self):
        t_build0 = time.monotonic()
        # phase ``build``: construction, up to the spoke threads' start
        with _trace.phase("build"):
            hub_comm, hub_opt, spoke_comms, sup, ckpt_mgr = self._build(
                t_build0)
            # the cylinders are threads on one device: they take it in
            # turns once its programs are long (solvers/turns.py)
            gate = (_turns.DeviceTurns() if _turns.in_order_device()
                    else None)
            threads, errors = self._start_spokes(spoke_comms, sup, gate)
        _trace.set_thread_track("hub")
        _turns.join(gate, "hub")
        try:
            hub_comm.main()
        except BaseException:
            # the spokes must not outlive a hub that raised
            _trace.set_thread_track(None)
            hub_comm.send_terminate()
            raise
        finally:
            # nobody waits for a hub that left its loop
            if gate is not None:
                gate.close()
            _turns.join(None, None)
        _trace.set_thread_track(None)
        # phase ``teardown``: hub main's return to run()'s return
        # (terminate, joins, finalize), back on the caller's track
        with _trace.phase("teardown"):
            hub_comm.send_terminate()
            # construction + hub loop: gap-based termination happened HERE;
            # the spoke teardown below (final bound-tightening passes,
            # lingering MILPs) can add minutes that are bookkeeping, not
            # time-to-certified-gap — benchmarks report this figure
            self.gap_wall_secs = time.monotonic() - t_build0
            self._teardown(hub_comm, hub_opt, spoke_comms, sup, ckpt_mgr,
                           threads, errors)
        return self

    def _build(self, t_build0):
        from .resilience import supervisor as _supervisor

        fabric = WindowFabric()

        # Hub opt + communicator (spin_the_wheel.py:92-116)
        hub = self.hub_dict
        hub_opt = hub["opt_class"](
            **self._cylinder_opt_kwargs(hub["opt_kwargs"]))
        hub_comm = hub["hub_class"](
            hub_opt, 0, fabric, spokes=self.list_of_spoke_dict,
            **hub.get("hub_kwargs", {}),
        )

        # Spoke opts + communicators; negotiate mailbox lengths
        spoke_comms = []
        for i, sd in enumerate(self.list_of_spoke_dict):
            opt = sd["opt_class"](**self._cylinder_opt_kwargs(sd["opt_kwargs"]))
            comm = sd["spoke_class"](
                opt, i + 1, fabric, **sd.get("spoke_kwargs", {}),
            )
            to_hub_len, to_spoke_len = comm.buffer_lengths()
            fabric.add_spoke(i + 1, to_spoke_len, to_hub_len)
            spoke_comms.append(comm)

        hub_comm.setup_hub()
        # resume + checkpointing (doc/resilience.md): bounds re-seed the
        # hub NOW (post-setup); PH state re-seats after the warm-up Iter0
        ckpt_mgr = self._wire_resilience(hub_comm, hub_opt)
        sup = _supervisor.SpokeSupervisor(
            fabric,
            {i + 1: c.__class__.__name__ for i, c in enumerate(spoke_comms)},
            timeout_secs=self._hub_options().get("spoke_timeout_secs"),
            grace_factor=float(self._hub_options().get(
                "spoke_timeout_grace", 8.0)))
        if spoke_comms:
            hub_comm.attach_supervisor(sup)
        global_toc(
            f"wheel constructed ({1 + len(spoke_comms)} cylinders) in "
            f"{time.monotonic() - t_build0:.1f}s", True)
        return hub_comm, hub_opt, spoke_comms, sup, ckpt_mgr

    @staticmethod
    def _start_spokes(spoke_comms, sup, gate=None):
        # Run spokes on threads, hub on the caller's (role dispatch analogue
        # of spin_the_wheel.py:119-127)
        threads = []
        errors = []

        def spoke_runner(comm, track, idx):
            # each cylinder thread is its own trace timeline — the
            # per-cylinder rows of the Perfetto view (doc/observability.md)
            _trace.set_thread_track(track)
            _turns.join(gate, "spoke")
            try:
                comm.main()
            except Exception as e:          # surface spoke crashes at join
                errors.append((comm.__class__.__name__, e))
                sup.note_error(idx, e)

        for i, comm in enumerate(spoke_comms):
            t = threading.Thread(
                target=spoke_runner,
                args=(comm, f"spoke{i + 1}:{comm.__class__.__name__}", i + 1),
                name=comm.__class__.__name__, daemon=True,
            )
            t.start()
            threads.append(t)
            sup.note_thread(i + 1, t)
        return threads, errors

    def _teardown(self, hub_comm, hub_opt, spoke_comms, sup, ckpt_mgr,
                  threads, errors):
        deadline = time.monotonic() + 900.0   # shared across all joins
        for i, t in enumerate(threads):
            # lost spokes get a short grace, not the whole deadline: a
            # crashed thread is already dead and a wedged one is exactly
            # what the supervisor told us not to wait for
            cap = 5.0 if sup.is_lost(i + 1) else deadline - time.monotonic()
            t.join(timeout=max(0.0, min(cap, deadline - time.monotonic())))
        hung = [t.name for t in threads if t.is_alive()]
        if hung:
            # A spoke stuck inside an uninterruptible host MILP (e.g. the
            # restricted EF's 120 s polish under host contention) must not
            # turn a certified run into an error: skip its finalize (it
            # cannot run concurrently with main), keep everything the hub
            # already accepted, and say so loudly.  Threads are daemons,
            # so process exit is not blocked.
            global_toc(
                f"WARNING: spoke thread(s) still running at teardown "
                f"(skipping their finalize): {hung}", True)
            self.hung_spokes = hung
        self.lost_spokes = sup.lost_names()
        self.spoke_errors = list(errors)
        if errors and self._hub_options().get("strict_spokes"):
            self._final_checkpoint(hub_comm, ckpt_mgr)
            raise RuntimeError(f"Spoke failures: {errors}")
        if errors:
            # graceful degradation (the default): the wheel completed on
            # the surviving bounders; the loss is loud, recorded, and on
            # the trace — but it is not an exception
            global_toc(
                f"WARNING: wheel degraded — spoke failures survived: "
                f"{[(n, repr(e)) for n, e in errors]}", True)

        # finalize: each cylinder flushes, then the hub collects (131-144).
        # Identity pairing (threads were created in spoke_comms order): a
        # hung instance must not suppress finalize for a healthy sibling
        # of the same class; a CRASHED spoke's finalize is skipped too
        # (its state is whatever the exception left behind).
        hub_comm.finalize()
        crashed = {idx for idx, (nm, why) in sup.lost().items()
                   if why == "crashed"}
        for i, (t, comm) in enumerate(zip(threads, spoke_comms)):
            if not t.is_alive() and (i + 1) not in crashed:
                comm.finalize()
        hub_comm.hub_finalize()
        self._warn_unconsumed_resume(hub_opt)
        self._final_checkpoint(hub_comm, ckpt_mgr)

        self.spcomm = hub_comm
        self.opt = hub_opt
        self.spoke_comms = spoke_comms
        self.spun = True

        # post-run caches (spin_the_wheel.py:166-217)
        self.BestInnerBound = hub_comm.BestInnerBound
        self.BestOuterBound = hub_comm.BestOuterBound
        self.local_nonant_cache = self._best_nonant_cache()
        self._write_result_sidecar()
        # a traced wheel banks its artifact NOW (not at interpreter exit:
        # the driver may SIGKILL a lingering process)
        _trace.flush_if_enabled()

    def _write_result_sidecar(self):
        """When TPUSPPY_RESULT_JSON names a path, bank {inner, outer,
        rel_gap} there — machine-checkable driver results, so harnesses
        (examples/run_all.py) can assert OBJECTIVES instead of exit codes
        (the reference harness's known liability, SURVEY §4)."""
        import json
        import os

        path = os.environ.get("TPUSPPY_RESULT_JSON")
        if not path:
            return
        ib, ob = float(self.BestInnerBound), float(self.BestOuterBound)
        if np.isfinite(ib) and np.isfinite(ob):
            rel_gap = abs(ib - ob) / (abs(ob) or 1.0)
        else:
            rel_gap = float("inf")
        with open(path, "w") as f:
            json.dump({"inner": ib, "outer": ob, "rel_gap": rel_gap}, f)

    # ---- solution access (spin_the_wheel.py:166-217) ------------------------
    def _best_nonant_cache(self):
        """(S, K) nonants of the best incumbent seen anywhere in the wheel."""
        best = getattr(self.opt, "best_xhat_cache", None)  # in-hub xhat ext
        best_val = getattr(self.opt, "best_inner_bound", np.inf)
        for comm in self.spoke_comms:
            if hasattr(comm, "best_snapshot"):
                v, cand = comm.best_snapshot()
            else:
                cand = getattr(comm, "best_solution_cache", None)
                v = getattr(comm, "best_inner_bound", np.inf)
            if cand is not None and v < best_val:
                best_val = v
                best = self.opt.nonants_of(cand)
        if best is None and self.opt.local_x is not None:
            best = self.opt.nonants_of(self.opt.local_x)
        return None if best is None else np.asarray(best)

    def write_first_stage_solution(self, solution_file_name: str):
        """CSV (or .npy) of root-stage nonant values (sputils.py:37-68)."""
        cache = self.local_nonant_cache
        if cache is None:
            raise RuntimeError("No solution available to write")
        tree = self.opt.tree
        root_slots = np.where(tree.nonant_stage == 1)[0]
        vals = cache[0, root_slots]
        if solution_file_name.endswith(".npy"):
            np.save(solution_file_name, vals)
            return
        names = self.opt.batch.names
        var_names = (
            self.opt.scenario_creator(
                names[0], **self.opt.scenario_creator_kwargs
            ).var_names
        )
        idx = tree.nonant_indices[root_slots]
        with open(solution_file_name, "w", newline="") as f:
            w = csv.writer(f)
            for j, v in zip(idx, vals):
                nm = var_names[j] if var_names else f"x[{j}]"
                w.writerow([nm, repr(float(v))])

    def write_tree_solution(self, directory_name: str):
        """Per-scenario nonant CSVs (spin_the_wheel.py:199-217)."""
        import os

        os.makedirs(directory_name, exist_ok=True)
        cache = self.local_nonant_cache
        if cache is None:
            raise RuntimeError("No solution available to write")
        for s, name in enumerate(self.opt.all_scenario_names):
            with open(os.path.join(directory_name, f"{name}.csv"), "w",
                      newline="") as f:
                w = csv.writer(f)
                for k in range(cache.shape[1]):
                    w.writerow([f"nonant[{k}]", repr(float(cache[s, k]))])


def spin_the_wheel(hub_dict, list_of_spoke_dict, comm_world=None):
    """Functional alias kept for reference parity (deprecated there too)."""
    ws = WheelSpinner(hub_dict, list_of_spoke_dict)
    ws.spin(comm_world)
    global_toc("Spinning complete", True)
    return ws


# ---- cross-process wheel over the C++ shm window service --------------------

def _child_env():
    """Child-process env for the spoke cylinders.

    A chip belongs to one process at a time and that process is the hub's,
    so spoke children run on ``JAX_PLATFORMS=cpu``.  They share the hub's
    persistent compilation cache (sibling cylinders compile identical
    solver programs; the XLA compile is paid once).
    """
    import os

    from .solvers import aot as _aot

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("JAX_COMPILATION_CACHE_DIR", _aot.compile_cache_dir())
    return env


def _ready_path(fabric_name, strata_rank):
    import os
    import tempfile

    tag = fabric_name.strip("/").replace("/", "_")
    return os.path.join(tempfile.gettempdir(), f"{tag}.{strata_rank}.ready")


def _spoke_worker(fabric_spec, spoke_dict, strata_rank):
    """Child-process entry: attach the window fabric, build this cylinder's
    opt, run its main loop (the per-rank role dispatch of
    spin_the_wheel.py:92-127, as an OS process instead of an MPI rank).
    ``fabric_spec`` is ("shm", name) or ("tcp", host, port, tag, secret) —
    the latter is exactly what a REMOTE host's spoke launcher passes
    (doc/multihost.md; ``tag`` names the readiness sentinel file and
    ``secret`` is the hub fabric's shared handshake token).
    A sentinel file marks construction-readiness for the parent's
    first-contact barrier (waiting for a bound Put instead would deadlock:
    xhat-style spokes publish only AFTER receiving hub data)."""
    kind = fabric_spec[0]
    if kind == "shm":
        from .runtime.window_service import ShmWindowFabric

        tag = fabric_spec[1]
        fabric = ShmWindowFabric(tag, attach=True)
    else:
        from .runtime.tcp_window_service import TcpWindowFabric

        _, host, port, tag, secret = fabric_spec
        fabric = TcpWindowFabric(connect=(host, port), secret=secret)
    opt = spoke_dict["opt_class"](**spoke_dict["opt_kwargs"])
    comm = spoke_dict["spoke_class"](
        opt, strata_rank, fabric, **spoke_dict.get("spoke_kwargs", {}))
    with open(_ready_path(tag, strata_rank), "w") as f:
        f.write("ready")
    _trace.set_thread_track(f"spoke{strata_rank}:{comm.__class__.__name__}")
    try:
        comm.main()
    finally:
        comm.finalize()


class MultiprocessWheelSpinner(WheelSpinner):
    """WheelSpinner whose spokes are separate OS processes over the C++
    shared-memory window service — true algorithm parallelism (SURVEY P3).

    The reference gives each cylinder its own process group and exchanges
    one-sided RMA windows (spin_the_wheel.py:219-237, spcommunicator.py:
    93-120); here each cylinder is an OS process and the windows are either
    seqlock shm mailboxes (runtime/csrc/window_service.cpp, single host) or
    the TCP box server (runtime/csrc/tcp_window_service.cpp, any host) with
    identical write-id / kill-sentinel semantics — pick with
    ``fabric="shm"|"tcp"``.  Spokes on OTHER hosts join a "tcp" wheel by
    connecting to ``(hub_host, fabric.port)`` — see doc/multihost.md.
    Intended for CPU cylinders or multi-host deployments where each process
    owns its own device slice; on the shared single-TPU dev box, the
    in-process (threaded) WheelSpinner remains the default.
    """

    def __init__(self, hub_dict, list_of_spoke_dict, fabric: str = "shm",
                 resume=None):
        super().__init__(hub_dict, list_of_spoke_dict, resume=resume)
        if fabric not in ("shm", "tcp"):
            raise ValueError(f"fabric must be 'shm' or 'tcp', got {fabric!r}")
        self.fabric_kind = fabric

    def run(self):
        import multiprocessing as mp
        import os
        import uuid

        hub = self.hub_dict
        hub_opt = hub["opt_class"](**hub["opt_kwargs"])

        # Length negotiation (the Send/Recv of spoke.py:34-58): buffer sizes
        # are functions of the shared model shape, so temporary spoke comms
        # around the HUB's opt compute them without building spoke opts.
        lengths = []
        for i, sd in enumerate(self.list_of_spoke_dict):
            tmp = sd["spoke_class"](hub_opt, i + 1, WindowFabric(),
                                    **sd.get("spoke_kwargs", {}))
            s2h, h2s = tmp.buffer_lengths()
            lengths.append((h2s, s2h))
        hub_opt.spcomm = None

        tag = f"/tpusppy_wheel_{os.getpid()}_{uuid.uuid4().hex[:8]}"
        if self.fabric_kind == "shm":
            from .runtime.window_service import ShmWindowFabric

            fabric = ShmWindowFabric(tag, spoke_lengths=lengths)
            spec = ("shm", tag)
        else:
            from .runtime.tcp_window_service import TcpWindowFabric

            fabric = TcpWindowFabric(spoke_lengths=lengths)
            spec = ("tcp", "127.0.0.1", fabric.port, tag, fabric.secret)

        ctx = mp.get_context("spawn")
        procs = []
        old_env = dict(os.environ)
        os.environ.clear()
        os.environ.update(_child_env())
        try:
            for i, sd in enumerate(self.list_of_spoke_dict):
                p = ctx.Process(
                    target=_spoke_worker, args=(spec, sd, i + 1),
                    name=sd["spoke_class"].__name__, daemon=True,
                )
                p.start()
                procs.append(p)
        finally:
            os.environ.clear()
            os.environ.update(old_env)

        hub_comm = hub["hub_class"](
            hub_opt, 0, fabric, spokes=self.list_of_spoke_dict,
            **hub.get("hub_kwargs", {}),
        )
        hub_comm.setup_hub()
        # resume + checkpointing live on the HUB side (it owns W and the
        # bounds); spokes re-seed from the first sync's payloads
        ckpt_mgr = self._wire_resilience(hub_comm, hub_opt)
        from .resilience import supervisor as _supervisor

        # death-only loss detection here: heartbeat gauges are
        # process-local (the obs registry does not cross the fork), so a
        # healthy child spoke idling between bounds would look exactly
        # like a wedged one — spoke_timeout_secs applies to the THREADED
        # spinner only (doc/resilience.md)
        sup = _supervisor.SpokeSupervisor(
            fabric,
            {i + 1: sd["spoke_class"].__name__
             for i, sd in enumerate(self.list_of_spoke_dict)},
            timeout_secs=None)
        for i, p in enumerate(procs):
            sup.note_process(i + 1, p)
        hub_comm.attach_supervisor(sup)
        # First-contact barrier: spawned cylinders cold-start a full python +
        # jax(+XLA compile) pipeline; a fast hub would otherwise finish and
        # kill them before they ever participate.  (MPI ranks start
        # together; process spawn does not.)  Readiness = the child
        # CONSTRUCTED its comm (sentinel file) — NOT its first bound Put,
        # which for xhat-style spokes only happens after hub data arrives.
        import time as _time

        wait = float(self.hub_dict.get("first_contact_wait", 900.0))
        t0 = _time.time()
        ready = [_ready_path(tag, i + 1)
                 for i in range(len(self.list_of_spoke_dict))]
        while _time.time() - t0 < wait:
            if all(os.path.exists(rp) for rp in ready):
                break
            if any(p.exitcode not in (None, 0) for p in procs):
                break
            _time.sleep(0.25)
        for rp in ready:
            try:
                os.remove(rp)
            except OSError:
                pass
        strict = bool(self._hub_options().get("strict_spokes"))
        try:
            try:
                hub_comm.main()
            finally:
                hub_comm.send_terminate()
            for i, p in enumerate(procs):
                p.join(timeout=5 if sup.is_lost(i + 1) else 300)
            hung = [p.name for p in procs if p.is_alive()]
            for p in procs:
                if p.is_alive():
                    p.terminate()
            if hung and strict:
                raise RuntimeError(
                    f"Spoke processes did not terminate: {hung}")
            bad = [(p.name, p.exitcode) for p in procs
                   if p.exitcode not in (0, None)]
            self.spoke_errors = bad
            if bad and strict:
                raise RuntimeError(f"Spoke process failures: {bad}")
            if bad or hung:
                # graceful degradation (the default, matching the threaded
                # spinner): the hub's accepted bounds stand
                global_toc(
                    f"WARNING: wheel degraded — spoke processes "
                    f"failed/hung: {bad + [(h, 'hung') for h in hung]}",
                    True)
        finally:
            # failure paths must not abandon the hub's results or leak the
            # POSIX shm segment
            hub_comm.finalize()
            hub_comm.hub_finalize()
            self._warn_unconsumed_resume(hub_opt)
            self._final_checkpoint(hub_comm, ckpt_mgr)
            self.lost_spokes = sup.lost_names()
            self.spcomm = hub_comm
            self.opt = hub_opt
            self.spoke_comms = []
            self.spun = True
            self.BestInnerBound = hub_comm.BestInnerBound
            self.BestOuterBound = hub_comm.BestOuterBound
            self.local_nonant_cache = self._best_nonant_cache()
            self._write_result_sidecar()
            fabric.close()
        return self
