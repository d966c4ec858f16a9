"""Warmup autotuner for the fused PH dispatch cadence.

The fused multi-iteration program (:func:`tpusppy.parallel.sharded.
make_ph_fused_step`) has two knobs: ``refresh_every`` (how many PH
iterations reuse one factorization — the math/amortization trade) and
``chunk`` (how many PH iterations one device dispatch carries — the
latency/watchdog trade).  The benchmark used to hard-code ``chunk=64``/
``refresh_every=16``; shapes whose sweeps are 16x costlier (farmer
crops_mult=4 vs 1) then run chunks far below what the worker watchdog
allows and pay dispatch round-trips they don't have to, while the static
worst-case cap (:func:`~tpusppy.parallel.sharded.fused_iteration_cap`,
every frozen iteration billed at its full ``max_iter`` sweep budget) is
~5-10x more conservative than measured reality.

:func:`autotune_fused` replaces both with measurement at warmup: for each
``refresh_every`` candidate it times a one-block probe dispatch, converts
the MEASURED seconds/iteration into a watchdog-safe chunk (``margin`` x
the dispatch target budget), confirms the rate at that chunk, and picks
the fastest cadence.  Probes are real PH iterations (the state advances —
warmup work is not wasted) and each probe is itself sized inside the
static worst-case cap, so a mistuned model can never push a probe past
the watchdog.

Grew out of ``scripts/profile_sweep_parts.py`` (whose jit/fetch timing
helper lives here now as :func:`time_jitted`); results feed ``bench.py``
and any driver that wants a per-shape cadence instead of a global
default.

Verdicts PERSIST: every fresh pick is banked in a JSON-able store keyed
by the same shape+settings+mesh key plus the jax version, saved
atomically to ``TPUSPPY_TUNE_CACHE`` when that knob names a file and
carried inside wheel checkpoints (:mod:`tpusppy.resilience.checkpoint`),
so repeated bench/wheel runs — and resumed ones — skip the warmup
probes entirely (:func:`export_state` / :func:`import_state` /
:func:`save_cache` / :func:`load_cache`).
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading
import time
from typing import Any

import numpy as np

from .obs import metrics as _metrics
from .obs import trace as _trace
from .parallel import sharded
from .solvers import aot as _aot
from .solvers import segmented as segmented_solvers


def _probe_event(kind: str, entry: dict):
    """One autotune probe verdict onto the "tune" track + counters —
    the autotuner's decisions (cadence picks, precision certifications,
    pipeline enables) are exactly the knobs a perf regression hunt needs
    on the timeline."""
    _metrics.inc(f"tune.{kind}_probes")
    if _trace.enabled():
        _trace.instant("tune", kind, **entry)


@dataclasses.dataclass
class TuneResult:
    chunk: int                 # picked dispatch size (PH iters per dispatch)
    refresh_every: int         # picked factorization cadence
    iters_per_sec: float       # measured at the picked (chunk, refresh)
    secs_per_iter: float
    sweeps_per_iter: float     # mean measured ADMM sweeps per PH iteration
    table: list                # per-candidate measurement dicts
    state: Any                 # PH state advanced by the probe iterations
    out: Any                   # last probe's PHStepOut
    # picked frozen-sweep matmul precision: the fastest mode whose probe
    # residuals certified against the full-precision reference ("highest"
    # when no lower mode certified or none were probed)
    precision: str = "highest"


_cache: dict = {}


# ---------------------------------------------------------------------------
# Persistent verdict store (disk + checkpoint interchange).
#
# Repeated bench/wheel runs used to re-pay the warmup probes (cadence,
# precision, pipeline) on every process start.  Verdicts are banked here
# keyed by ``repr`` of the SAME shape+settings+mesh key the in-memory
# cache uses, partitioned by jax version (a jaxlib bump can change every
# measured rate), and persisted to ``TPUSPPY_TUNE_CACHE`` (a JSON file)
# with the engine-wide atomic write-tmp-then-rename discipline.  The
# resilience checkpoint engine snapshots/reseeds the same store
# (:func:`export_state` / :func:`import_state`), so a resumed wheel
# skips its warmup probes too.  Multiple processes banking concurrently
# are last-writer-wins per save — acceptable for a cache whose entries
# are independently recomputable.
# ---------------------------------------------------------------------------
# Schema v2 (the megakernel PR): a "megastep" verdict kind joined the
# store, and the fused/pipeline KEYS changed — ``ADMMSettings`` grew the
# ``megastep`` field, which rides every settings repr in a key — so a v1
# file's verdicts could otherwise never be distinguished from current
# ones.  ``import_state`` drops foreign-version state wholesale (tolerant
# load: an old cache file is just a cold cache, never a crash and never a
# stale cadence/pipeline verdict served to a megakernel-enabled run).
_PERSIST_VERSION = 2
# "aot" (the executable-cache PR): per-fused-key list of AOT executable
# cache keys compiled/loaded while that verdict was measured — a disk hit
# on the fused verdict then PRE-WARMS those executables in a background
# thread before iter0 (tpusppy/solvers/aot.py).  Absent in older v2
# files, tolerated (just no prewarm) — no schema bump needed: fused/
# pipeline/megastep keys are unchanged.
# "bound_cadence" (the in-wheel certification PR): per-shape verdict for
# how often a self-certifying megastep window runs its fused bound pass
# (doc/pipeline.md "In-wheel certification").  Absent in older v2 files,
# tolerated — existing kinds' keys are unchanged, no schema bump.
# "integer" (the batched integer wheel PR, doc/integer.md): per-shape
# verdict for the rounding-sweep width K (how many ladder thresholds the
# integer bound pass evaluates) and its window cadence, picked from the
# measured marginal pass cost.  Absent in older files, tolerated.
# "batched" (continuous batching, doc/serving.md): per-family verdict
# for the tenant-batched megastep's slot count K, picked so the fused
# window's measured per-slot marginal cost keeps the whole dispatch
# under the watchdog budget.  Absent in older files, tolerated.
_PERSIST_KINDS = ("fused", "pipeline", "megastep", "aot", "bound_cadence",
                  "integer", "batched")
_persist: dict = {k: {} for k in _PERSIST_KINDS}
_persist_lock = threading.Lock()
_disk_loaded_from: str | None = None


def _jax_version() -> str:
    try:
        import jax

        return str(jax.__version__)
    except ImportError:             # key-building unit tests without jax
        return "none"


_cache_path_override: str | None = None


def set_cache_path(path: str | None):
    """Programmatic override of the TPUSPPY_TUNE_CACHE knob (what
    ``Config.tune_cache`` routes through — scoped to this process's tune
    module instead of leaking an env var into every child)."""
    global _cache_path_override
    _cache_path_override = str(path) if path else None


def cache_path() -> str | None:
    """The armed persistent-cache path (programmatic override first, then
    TPUSPPY_TUNE_CACHE; empty/unset disables persistence — tests stay
    hermetic by default)."""
    return (_cache_path_override
            or os.environ.get("TPUSPPY_TUNE_CACHE") or None)


def export_state() -> dict:
    """JSON-able snapshot of every banked verdict (fused + pipeline) —
    what wheel checkpoints carry so a resume skips warmup probes."""
    with _persist_lock:
        out = {"version": _PERSIST_VERSION, "jax": _jax_version()}
        out.update({k: dict(_persist[k]) for k in _PERSIST_KINDS})
        return out


def import_state(state: dict):
    """Merge a snapshot produced by :func:`export_state` (same-jax-version
    entries only; foreign measurements must not masquerade as local).

    Foreign SCHEMA versions are dropped wholesale (tolerant load): a
    pre-megakernel (v1) store's fused/pipeline verdicts were keyed
    without the ``ADMMSettings.megastep`` field and must never be served
    to a megakernel-enabled run — an old file is just a cold cache."""
    if not state or state.get("jax") not in (None, _jax_version()):
        return
    if state.get("version") != _PERSIST_VERSION:
        _metrics.inc("tune.disk_version_skips")
        return
    with _persist_lock:
        for kind in _PERSIST_KINDS:
            _persist[kind].update(state.get(kind) or {})


def save_cache(path: str | None = None) -> str | None:
    """Atomically write the banked verdicts to ``path`` (default: the
    TPUSPPY_TUNE_CACHE knob).  No-op (None) when no path is armed."""
    path = path or cache_path()
    if not path:
        return None
    from .resilience.checkpoint import atomic_write_json

    return atomic_write_json(path, export_state())


def load_cache(path: str | None = None) -> int:
    """Load a verdict file into the in-process store; returns the number
    of entries now banked.  Files from another jax version are ignored
    (their measurements are not this toolchain's)."""
    path = path or cache_path()
    if not path or not os.path.exists(path):
        return 0
    try:
        with open(path) as f:
            state = json.load(f)
    except (OSError, json.JSONDecodeError):
        return 0                 # a torn/foreign file is just a cold cache
    import_state(state)
    with _persist_lock:
        return sum(len(_persist[k]) for k in _PERSIST_KINDS)


def _maybe_load_disk():
    """Lazy one-shot load of the armed cache file (re-armed paths reload)."""
    global _disk_loaded_from
    path = cache_path()
    if path and path != _disk_loaded_from:
        _disk_loaded_from = path
        n = load_cache(path)
        if n:
            _metrics.inc("tune.disk_entries_loaded", n)


def _persist_get(kind: str, key_str: str):
    _maybe_load_disk()
    with _persist_lock:
        return _persist[kind].get(key_str)


def _persist_put(kind: str, key_str: str, entry: dict):
    with _persist_lock:
        _persist[kind][key_str] = entry
    if cache_path():
        try:
            save_cache()
        except OSError as e:     # a read-only cache dir must not kill tuning
            _metrics.inc("tune.disk_save_errors")
            from .obs.log import get_logger

            get_logger("tune").warning(
                "persistent cache save failed: %r", e)


def reset_persist():
    """Drop banked verdicts (test isolation)."""
    global _disk_loaded_from, _cache_path_override
    with _persist_lock:
        for kind in _PERSIST_KINDS:
            _persist[kind].clear()
    _mega_cache.clear()
    _bound_cadence_cache.clear()
    _integer_cache.clear()
    _disk_loaded_from = None
    _cache_path_override = None


def prewarm_aot(background: bool = False) -> int:
    """Pre-warm the AOT executable cache from every banked "aot" verdict
    (plus anything else in the cache dir): call before iter0/the first
    program build.  SYNCHRONOUS by default — on this toolchain the
    executable loader is only reliable in a clean XLA state (a big
    compile first can leave deserialization refusing entries with
    "Symbols not found"), so front-loading the deserializes beats
    overlapping them.  ``background=True`` restores the overlapped
    daemon-thread load (what a tune-cache disk hit uses mid-flow, where
    the fused-program load is the first XLA work anyway).  Returns the
    number of banked keys (0 = nothing armed/banked)."""
    _maybe_load_disk()
    with _persist_lock:
        keys = [k for entry in _persist["aot"].values()
                for k in (entry.get("keys") or [])]
    if not _aot.enabled():
        return 0
    # banked keys load first, then the directory sweep picks up programs
    # no tune verdict recorded (already-loaded keys are skipped).  The
    # banked list is capped like the sweep: a many-rung ladder cache can
    # bank far more shapes than this process will ever call, and every
    # load costs pre-iter0 wall + resident memory.
    want = list(dict.fromkeys(keys))[:_aot.PREWARM_MAX_FILES] or None
    if background:
        def _load():
            if want:
                _aot.prewarm(want)
            _aot.prewarm(None)

        threading.Thread(target=_load, name="aot-prewarm",
                         daemon=True).start()
    else:
        if want:
            _aot.prewarm(want)
        _aot.prewarm(None)
    return len(keys)


def _fetch(x):
    """Host fetch as the timing fence: the copy cannot complete before
    the dispatch that produced ``x`` has."""
    return np.asarray(x)


def time_jitted(fn, *args, reps=20):
    """Milliseconds per call of an already-jitted ``fn`` (fetch-fenced);
    the sweep-part profiler's timing core (scripts/profile_sweep_parts)."""
    import jax
    import jax.numpy as jnp

    out = fn(*args)
    first = out[0] if isinstance(out, tuple) else out
    _fetch(jnp.sum(first) if isinstance(first, jax.Array) else first)
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args)
    first = out[0] if isinstance(out, tuple) else out
    _fetch(jnp.sum(first) if isinstance(first, jax.Array) else first)
    return (time.time() - t0) / reps * 1e3


def _tune_key(arr, settings, mesh, axis, prox_on, refresh_candidates,
              max_chunk, target_secs, margin, precision_candidates,
              certify_factor):
    # the shape+settings+mesh prefix is THE shared key builder
    # (aot.family_parts): the executable cache keys embed the same tuple,
    # so tune-cache keys and AOT-cache keys cannot silently drift
    return _aot.family_parts(arr, settings, mesh, axis) + (
        float(prox_on), tuple(refresh_candidates), max_chunk, target_secs,
        margin, tuple(precision_candidates or ()), certify_factor)


def autotune_fused(nonant_idx, settings, arr, state, mesh=None,
                   axis: str = "scen", prox_on=1.0,
                   refresh_candidates=(8, 16, 32), max_chunk: int = 256,
                   target_secs: float | None = None, margin: float = 0.5,
                   budget_s: float = 120.0, cache: bool = True,
                   precision_candidates=None, certify_factor: float = 1.5):
    """Measure-and-pick (chunk, refresh_every[, sweep precision]) for
    these shapes.

    Returns a :class:`TuneResult` (with the probe-advanced ``state``), or
    ``None`` when no candidate fits even a one-block probe under the
    static worst-case cap (segmentation regime — use the step pair).

    ``target_secs``: per-dispatch wall budget (defaults to the segmented
    dispatch target, itself 2x under the worker watchdog); the picked
    chunk keeps a measured dispatch at ``margin * target_secs``.
    ``budget_s`` bounds total tuning wall-clock — candidates that don't
    fit the remaining budget fall back to their probe measurement.

    ``precision_candidates`` (e.g. ``("default", "high")``): after the
    cadence pick, probe each lowered frozen-sweep precision mode at the
    picked cadence and CERTIFY it — its probe's final worst residual must
    stay within ``certify_factor`` x the full-precision reference probe's
    (floored at eps).  The fastest certified mode wins
    (:attr:`TuneResult.precision`); state advances only along certified
    iterates (uncertified probes run donate-free from a kept state and
    are discarded).  None/empty skips the stage entirely.  Cost note: the
    stage compiles one fresh donate-free program per probed mode PLUS a
    full-precision reference (the budget gates model run time, not
    compiles — the persistent XLA cache amortizes those across runs);
    shapes with minutes-long compiles should pin the mode instead.

    The cache (keyed on shapes + settings + mesh width + the tuning
    parameters, budget included) makes repeat calls free but returns the
    CALLER's state untouched — probe iterations only advance the state on
    a cache miss.
    """
    if target_secs is None:
        # honor the same override slot the static cap and probes obey
        # (sharded._DISPATCH_TARGET_SECS, None = the segmented default): a
        # stricter worker watchdog must also shrink the MEASURED chunk
        target_secs = (sharded._DISPATCH_TARGET_SECS
                       if sharded._DISPATCH_TARGET_SECS is not None
                       else segmented_solvers._DISPATCH_TARGET_SECS)
    key = _tune_key(arr, settings, mesh, axis, prox_on, refresh_candidates,
                    max_chunk, target_secs, margin, precision_candidates,
                    certify_factor)
    if cache and key in _cache:
        hit = _cache[key]
        return dataclasses.replace(hit, state=state, out=None)
    if cache:
        # persistent verdicts (TPUSPPY_TUNE_CACHE / resumed checkpoints):
        # a banked same-key pick skips the whole warmup probe ladder
        dk = _persist_get("fused", repr(key))
        if dk is not None:
            _metrics.inc("tune.disk_hits")
            # pre-warm THIS verdict's banked executables, synchronously:
            # a background load here would race the caller's imminent
            # plain-jit compiles, which is exactly the deserialize-vs-
            # compile crash aot._xla_work_lock documents (the lock only
            # covers aot's own work).  The list is a handful of keys and
            # each load is ~ms against the compile it replaces.
            ak = _persist_get("aot", repr(key))
            if ak and ak.get("keys"):
                _aot.prewarm(ak["keys"][:_aot.PREWARM_MAX_FILES])
            res = TuneResult(
                chunk=int(dk["chunk"]), refresh_every=int(dk["refresh_every"]),
                iters_per_sec=float(dk["iters_per_sec"]),
                secs_per_iter=float(dk["secs_per_iter"]),
                sweeps_per_iter=float(dk["sweeps_per_iter"]),
                table=list(dk.get("table", [])) + [{"from": "disk_cache"}],
                state=state, out=None,
                precision=str(dk.get("precision", "highest")))
            _cache[key] = dataclasses.replace(res, state=None, out=None)
            return res

    t_start = time.time()
    aot_mark = _aot.session_mark()
    table = []
    best = None
    out = None
    for r in refresh_candidates:
        r = int(r)
        if r > max_chunk:
            # max_chunk is the caller's per-dispatch bound; even the
            # one-block probe of this candidate would exceed it
            table.append({"refresh_every": r, "skipped": "max_chunk"})
            _probe_event("cadence", table[-1])
            continue
        cap = sharded.fused_iteration_cap(arr, settings, mesh, r)
        if cap < r:
            table.append({"refresh_every": r, "skipped": "static cap"})
            _probe_event("cadence", table[-1])
            continue
        fused_probe = sharded.make_ph_fused_step(
            nonant_idx, settings, mesh, axis, chunk=r, refresh_every=r,
            collect="trace")
        state, trace = fused_probe(state, arr, prox_on)   # compile + run
        iters_tr = _fetch(trace.iters)
        t0 = time.time()
        state, trace = fused_probe(state, arr, prox_on)
        iters_tr = _fetch(trace.iters)
        dt = time.time() - t0
        out = trace
        spi = dt / r
        sweeps = float(iters_tr.mean())
        # measured watchdog-safe chunk: margin * target over the measured
        # per-iteration cost, whole refresh blocks only
        c = int(margin * target_secs / max(spi, 1e-9)) // r * r
        c = max(r, min(c, max_chunk))
        entry = {"refresh_every": r, "probe_chunk": r,
                 "probe_secs_per_iter": round(spi, 6),
                 "sweeps_per_iter": round(sweeps, 1), "chunk": c}
        rate = 1.0 / spi
        remaining = budget_s - (time.time() - t_start)
        if c > r and c * spi * 2.5 < remaining:
            # confirm at the picked chunk (compile + one timed dispatch):
            # the dispatch amortization is the whole point, so rank on it
            fused_c = sharded.make_ph_fused_step(
                nonant_idx, settings, mesh, axis, chunk=c, refresh_every=r,
                collect="trace")
            state, trace = fused_c(state, arr, prox_on)
            _fetch(trace.conv)
            t0 = time.time()
            state, trace = fused_c(state, arr, prox_on)
            iters_tr = _fetch(trace.iters)
            dt = time.time() - t0
            out = trace
            rate = c / dt
            sweeps = float(iters_tr.mean())
            entry["confirmed_iters_per_sec"] = round(rate, 4)
            entry["sweeps_per_iter"] = round(sweeps, 1)
        entry["iters_per_sec"] = round(rate, 4)
        table.append(entry)
        _probe_event("cadence", entry)
        if best is None or rate > best[0]:
            best = (rate, c, r, sweeps)
        if time.time() - t_start > budget_s:
            break
    if best is None:
        return None
    rate, c, r, sweeps = best

    # ---- precision stage: fastest mode whose residuals certify ----------
    precision = settings.sweep_precision or "highest"
    # each probe costs ~2 dispatches (compile + timed) of c iterations at
    # the measured rate; skip the whole stage — reference probe included —
    # when the cadence stage already spent the budget.  The skip is
    # RECORDED: the returned precision is then just the caller's setting,
    # not a certified pick (bench treats a pin the same way)
    est_probe = 2.5 * c / max(rate, 1e-9)
    stage_fits = budget_s - (time.time() - t_start) > 2 * est_probe
    if precision_candidates and not stage_fits:
        table.append({"precision_stage": "skipped", "reason": "budget"})
    if precision_candidates and stage_fits:
        eps_floor = max(settings.eps_abs, settings.eps_rel)

        def _probe_mode(st_m):
            """(rate, worst_final_residual, sweeps, state, trace) of one
            timed dispatch at the picked cadence; donate=False so every
            probe starts from the same kept ``state``."""
            fused_m = sharded.make_ph_fused_step(
                nonant_idx, st_m, mesh, axis, chunk=c, refresh_every=r,
                collect="trace", donate=False)
            fused_m(state, arr, prox_on)           # compile
            t0 = time.time()
            st_out, tr = fused_m(state, arr, prox_on)
            pri = _fetch(tr.pri_res)
            dt = time.time() - t0
            dua = _fetch(tr.dua_res)
            worst = float(max(pri[-1].max(), dua[-1].max()))
            return (c / dt, worst, float(_fetch(tr.iters).mean()),
                    st_out, tr)

        # the certification reference is ALWAYS full precision, whatever
        # mode the caller's settings carry (the documented contract —
        # certifying a lowered mode against another lowered floor would
        # be vacuous)
        st_ref = dataclasses.replace(settings, sweep_precision=None)
        ref_rate, ref_worst, ref_sweeps, ref_state, ref_tr = _probe_mode(
            st_ref)
        bar = certify_factor * max(ref_worst, eps_floor)
        table.append({"precision": "highest", "iters_per_sec":
                      round(ref_rate, 4), "worst_residual": ref_worst,
                      "reference": True})
        # a caller whose settings ALREADY carry a lowered mode gets that
        # mode certified like any candidate (the cadence stage measured
        # with it, so it must earn its place or be replaced)
        caller_mode = settings.sweep_precision or "highest"
        cands = [m for m in precision_candidates if m != "highest"]
        if caller_mode != "highest" and caller_mode not in cands:
            cands.insert(0, caller_mode)
        # reference pick keeps the cadence stage's donated measurements
        # (rate/sweeps/state/out stay untouched unless a lowered mode
        # wins); candidates race the reference under IDENTICAL probe
        # conditions (donate=False), so the comparison is apples-to-apples
        precision = "highest"
        pick = None
        best_rate = ref_rate
        for mode in cands:
            remaining = budget_s - (time.time() - t_start)
            if est_probe > remaining:
                table.append({"precision": mode, "skipped": "budget"})
                continue
            st_m = dataclasses.replace(settings, sweep_precision=mode)
            rate_m, worst_m, sweeps_m, st_out, tr_m = _probe_mode(st_m)
            ok = np.isfinite(worst_m) and worst_m <= bar
            table.append({"precision": mode,
                          "iters_per_sec": round(rate_m, 4),
                          "worst_residual": worst_m, "certified": bool(ok)})
            _probe_event("precision", table[-1])
            _metrics.inc("tune.precision_certified" if ok
                         else "tune.precision_rejected")
            if ok and rate_m > best_rate:
                best_rate = rate_m
                pick = (rate_m, mode, sweeps_m, st_out, tr_m)
        if pick is not None:
            rate, precision, sweeps, state, out = pick
        elif caller_mode != "highest":
            # no lowered mode certified, but the cadence stage measured at
            # the caller's (now-rejected) mode — report the full-precision
            # probe's figures so the returned rate matches the returned
            # precision
            rate, sweeps, state, out = (ref_rate, ref_sweeps, ref_state,
                                        ref_tr)

    last = None if out is None else sharded.PHStepOut(
        *(a[-1] for a in out))
    res = TuneResult(chunk=c, refresh_every=r, iters_per_sec=rate,
                     secs_per_iter=1.0 / rate, sweeps_per_iter=sweeps,
                     table=table, state=state, out=last,
                     precision=precision)
    if cache:
        _cache[key] = dataclasses.replace(res, state=None, out=None)
        _persist_put("fused", repr(key), {
            "chunk": int(c), "refresh_every": int(r),
            "iters_per_sec": float(rate), "secs_per_iter": float(1.0 / rate),
            "sweeps_per_iter": float(sweeps), "precision": str(precision),
            "table": _json_safe(table)})
        # bank the AOT executable-cache keys the probe programs resolved
        # under (the "aot" persist kind): a future run's disk hit on this
        # verdict prewarms exactly those executables before iter0
        aot_keys = _aot.session_keys_since(aot_mark)
        if aot_keys:
            _persist_put("aot", repr(key), {"keys": aot_keys})
    return res


def _json_safe(obj):
    """Probe tables carry numpy scalars; the persistent store is JSON."""
    if isinstance(obj, dict):
        return {str(k): _json_safe(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_json_safe(v) for v in obj]
    if isinstance(obj, (np.floating, np.integer)):
        return obj.item()
    if isinstance(obj, (str, int, float, bool)) or obj is None:
        return obj if obj == obj else None     # NaN -> null (strict JSON)
    return repr(obj)


@dataclasses.dataclass
class PipelineTune:
    enabled: bool              # speculation pays for this shape
    seg_secs: float            # measured wall of one frozen re-dispatch
    fetch_secs: float          # measured stop-stats RPC round-trip
    waste_flops: float         # model flops of one discarded segment
    sol: Any                   # the probe segment's solution (real work —
    # callers may keep it as their next warm state)


_pipe_cache: dict = {}


def autotune_pipeline(run_segment, sol, shape, seg_f, pay_factor=1.0,
                      reps=3, cache=True, sparse_factor=1.0):
    """Measure whether the speculative frozen continuation pays for a
    shape, and record the verdict in the segmented dispatch policy.

    The pipelined continuation (``segmented.continue_frozen``) hides one
    stop-stats fetch RPC behind each segment's device compute, at a
    worst-case cost of one discarded segment per solve.  Two measurements
    decide whether that trade wins:

    - ``fetch_secs``: the stop-stats round-trip on an ALREADY-computed
      solution — pure host<->device latency, the thing speculation hides;
    - ``seg_secs``: one frozen re-dispatch (``run_segment(sol.raw)``)
      end to end — the speculative unit of work, and the worst-case waste.

    Speculation pays when a segment costs at least ``pay_factor`` x the
    fetch: the latency hidden per segment then rivals or exceeds the
    bounded waste.  Tiny shapes whose segment is CHEAPER than the fetch
    (farmer-sized batches) gain nothing — the fetch
    dominates wall time with or without overlap — and are disabled via
    :func:`tpusppy.solvers.segmented.set_pipeline_policy`, which
    ``solve_frozen_segmented`` / ``solve_factored_segmented`` and the
    sharded step pair consult per shape.

    ``shape`` is (S, n, m) in the DISPATCH-model convention of
    :func:`segmented.dispatch_segments`: the PER-DEVICE scenario count on
    a mesh (what one segment actually sweeps — and the key the sharded
    step pair queries), the global S on the single-device host path.
    The probe segments (a compile-absorbing warmup plus the timed
    dispatch) are REAL work — the returned ``sol`` advanced by two
    segments; keep it as the next warm state.  Cached per (shape, seg_f,
    pay_factor); repeat calls are free, re-record the verdict, and do
    not re-advance the solution.  This is an opt-in measurement utility
    for drivers and benches — nothing calls it implicitly;
    unmeasured shapes default to speculating (waste bounded + billed).
    """
    from .solvers import admm, hostsync
    from .solvers import flops as flops_model
    from .solvers import segmented

    S, n, m = (int(v) for v in shape)
    key = (S, n, m, int(seg_f), float(pay_factor))
    if cache and key in _pipe_cache:
        hit = _pipe_cache[key]
        # re-apply the verdict: the policy dict in `segmented` is a
        # separate store and may have been cleared/reset since it was
        # recorded — a cached verdict that is not re-recorded would
        # silently fall back to the default
        segmented.set_pipeline_policy(S, n, m, hit.enabled)
        return dataclasses.replace(hit, sol=sol)
    if cache:
        dk = _persist_get("pipeline", repr(key))
        if dk is not None:
            _metrics.inc("tune.disk_hits")
            hit = PipelineTune(
                enabled=bool(dk["enabled"]), seg_secs=float(dk["seg_secs"]),
                fetch_secs=float(dk["fetch_secs"]),
                waste_flops=float(dk["waste_flops"]), sol=None)
            _pipe_cache[key] = hit
            segmented.set_pipeline_policy(S, n, m, hit.enabled)
            return dataclasses.replace(hit, sol=sol)

    # fetch latency: dispatch + host read of a FRESH stop-stats program
    # per rep — re-fetching one array would time jax's cached host value
    # (ArrayImpl memoizes its numpy value after the first transfer), not
    # the RPC.  The stats compute is a handful of reductions, negligible
    # against the round-trip this exists to measure; the first (warmup)
    # call absorbs the compile.
    hostsync.fetch(admm.stop_stats(sol))
    t0 = time.time()
    for _ in range(max(1, reps)):
        hostsync.fetch(admm.stop_stats(sol))
    fetch_secs = (time.time() - t0) / max(1, reps)

    # frozen re-dispatch cost: a compile-absorbing WARMUP segment first
    # (the frozen program is a different executable from whatever
    # produced ``sol``, and 0.1-10 s of one-time XLA compile inside the
    # timed window would bias every verdict toward "enabled" — the same
    # reason autotune_fused warms its probes), then one timed dispatch,
    # fetch-fenced end to end (includes its own stats fetch — exactly
    # what a serial continuation step costs).  Both segments are real
    # work: the returned sol advanced by two.
    probe = run_segment(sol.raw)
    hostsync.fetch(admm.stop_stats(probe))
    t0 = time.time()
    probe = run_segment(probe.raw)
    hostsync.fetch(admm.stop_stats(probe))
    seg_secs = time.time() - t0

    # the verdict weighs the segment's COMPUTE cost (what a discarded
    # speculative segment wastes) against the RPC it hides: seg_secs
    # includes its own fence fetch, so comparing it raw would be >=
    # fetch_secs by construction and the tiny-shape disable could never
    # fire at the default pay_factor
    compute_secs = max(0.0, seg_secs - fetch_secs)
    enabled = compute_secs >= pay_factor * fetch_secs
    segmented.set_pipeline_policy(S, n, m, enabled)
    _probe_event("pipeline", {"S": S, "n": n, "m": m, "enabled": enabled,
                              "seg_secs": seg_secs,
                              "fetch_secs": fetch_secs})
    res = PipelineTune(
        enabled=enabled, seg_secs=seg_secs, fetch_secs=fetch_secs,
        waste_flops=flops_model.speculation_flops(
            S, n, m, seg_f, sparse_factor=sparse_factor),
        sol=probe)
    if cache:
        _pipe_cache[key] = dataclasses.replace(res, sol=None)
        _persist_put("pipeline", repr(key), {
            "enabled": bool(enabled), "seg_secs": float(seg_secs),
            "fetch_secs": float(fetch_secs),
            "waste_flops": float(res.waste_flops)})
    return res


# ---------------------------------------------------------------------------
# Megastep stage: pick the wheel-megakernel width N per shape from MEASURED
# dispatch overhead (ROADMAP item 4's "use obs dispatch-overhead data to
# pick N").  Verdicts persist under the "megastep" kind, keyed like the
# cadence/precision/pipeline verdicts, and the PH hub's auto path
# (PHBase._megastep_request) consults them via :func:`megastep_verdict`.
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class MegastepTune:
    n: int                    # picked megastep width (iterations/dispatch)
    per_iter_secs: float      # marginal device cost per fused iteration
    overhead_secs: float      # dispatch + packed-fetch overhead per window
    overhead_pct_at_n: float  # modeled dispatch_overhead_pct at the pick


_mega_cache: dict = {}


def _mega_key(shape, settings=None):
    """Megastep verdict key: the :func:`tpusppy.solvers.aot.
    shape_family_parts` family identity per shape — ``shape`` is one
    (S, n, m) triple or, for a bucketed family, a tuple of per-bucket
    triples.  S (per bucket) and the settings ride the key, so the
    ladder's shared ``TPUSPPY_TUNE_CACHE`` can never serve an S=1000
    verdict to an S=10000 run (the family_parts drift guard in
    tests/test_tune.py pins the structure against aot's)."""
    if shape and isinstance(shape[0], (tuple, list, np.ndarray)):
        return tuple(_aot.shape_family_parts(s, n, m, settings)
                     for s, n, m in shape)
    S, n, m = shape
    return _aot.shape_family_parts(S, n, m, settings)


def _mega_disk_lookup(key):
    """Rehydrate a banked megastep verdict from the persistent store into
    ``_mega_cache`` (None when the store holds none for ``key``)."""
    dk = _persist_get("megastep", repr(key))
    if dk is None:
        return None
    _metrics.inc("tune.disk_hits")
    res = MegastepTune(
        n=int(dk["n"]), per_iter_secs=float(dk["per_iter_secs"]),
        overhead_secs=float(dk["overhead_secs"]),
        overhead_pct_at_n=float(dk["overhead_pct_at_n"]))
    _mega_cache[key] = res
    return res


def megastep_verdict(S, n=None, m=None, settings=None) -> int | None:
    """Banked autotuned megastep width for a shape (None = no verdict —
    the hub then falls back to the refresh-window default).  ``S`` may be
    the full shape key — one (S, n, m) triple or a tuple of per-bucket
    triples — with ``n``/``m`` omitted."""
    shape = (S, n, m) if n is not None else S
    key = _mega_key(shape, settings)
    hit = _mega_cache.get(key) or _mega_disk_lookup(key)
    return hit.n if hit is not None else None


def autotune_megastep(run_window, shape, n_cap, target_pct: float = 1.0,
                      n_probe: int | None = None, cache: bool = True,
                      settings=None):
    """Measure the per-window dispatch+fetch overhead of the wheel
    megakernel and pick the smallest N that amortizes it below
    ``target_pct`` percent of the window wall (the farmer-m1
    ``dispatch_overhead_pct < 1%`` target), clamped to ``n_cap`` (the
    watchdog cap — :func:`segmented.megastep_cap` — and/or the refresh
    window).

    ``run_window(n)`` executes ONE megastep window of up to ``n`` wheel
    iterations end to end (dispatch + packed measurement fetch) and
    returns the executed iteration count.  Probe windows are REAL wheel
    iterations — callers apply each window's measurement normally, so
    warmup work is never wasted (the autotune_fused posture).  Three
    windows run: a compile-absorbing n=1 warmup, a timed n=1 window (the
    overhead + one iteration), and a timed ``n_probe`` window (the
    marginal per-iteration cost).  The verdict is banked under the
    "megastep" persist kind, so repeated runs (and resumed wheels) skip
    the probes.
    """
    key = _mega_key(shape, settings)
    if cache:
        hit = _mega_cache.get(key) or _mega_disk_lookup(key)
        if hit is not None:
            return hit

    n_cap = max(1, int(n_cap))
    if n_probe is None:
        n_probe = max(2, min(n_cap, 8))
    n_probe = max(2, min(int(n_probe), max(2, n_cap)))
    run_window(1)                       # compile-absorbing warmup window
    t0 = time.time()
    run_window(1)
    t1 = time.time() - t0               # overhead + 1 iteration
    t0 = time.time()
    ex = int(run_window(n_probe))
    tN = time.time() - t0               # overhead + ex iterations
    if ex <= 1:
        # degenerate probe (the window converged, or its first iterate
        # failed the in-scan acceptance test): (tN - t1) measures noise,
        # and a verdict derived from it would permanently steer this
        # shape via the persistent store — return the conservative
        # "don't megastep" answer WITHOUT banking, so the next run
        # re-probes under normal conditions
        _probe_event("megastep", {"shape": repr(shape),
                                  "skipped": "degenerate probe",
                                  "executed": ex})
        return MegastepTune(n=1, per_iter_secs=max(tN, 1e-9),
                            overhead_secs=max(t1, 0.0),
                            overhead_pct_at_n=100.0)
    per_iter = max((tN - t1) / max(ex - 1, 1), 1e-9)
    overhead = max(t1 - per_iter, 0.0)
    f = max(target_pct, 1e-3) / 100.0
    # overhead_pct(N) = o / (o + N*per_iter) <= f  =>  N >= o(1-f)/(f*p)
    n_pick = int(np.ceil(overhead * (1.0 - f) / (f * per_iter)))
    n_pick = max(1, min(n_pick, n_cap))
    pct = 100.0 * overhead / (overhead + n_pick * per_iter)
    res = MegastepTune(n=n_pick, per_iter_secs=per_iter,
                       overhead_secs=overhead, overhead_pct_at_n=pct)
    _probe_event("megastep", {"shape": repr(shape), "pick": n_pick,
                              "per_iter_secs": per_iter,
                              "overhead_secs": overhead,
                              "overhead_pct_at_n": pct})
    if cache:
        _mega_cache[key] = res
        _persist_put("megastep", repr(key), {
            "n": int(n_pick), "per_iter_secs": float(per_iter),
            "overhead_secs": float(overhead),
            "overhead_pct_at_n": float(pct)})
    return res


# ---------------------------------------------------------------------------
# Bound-cadence stage (in-wheel certification, doc/pipeline.md): pick how
# often a self-certifying megastep window runs its fused bound pass from
# the MEASURED marginal bound-pass cost vs the window wall.  Fresh bounds
# every window close the certified gap soonest; when the pass costs a
# meaningful fraction of the window (the xhat frozen evaluation is about
# one extra frozen iteration), spacing it every k windows trades bound
# staleness (at most k-1 windows of gap-closing lag) for wheel
# throughput.  Verdicts persist under the "bound_cadence" kind on the
# same shape+settings key family as the megastep stage.
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class BoundCadenceTune:
    every: int                # bound pass every k-th megastep window
    bound_secs: float         # marginal cost of one fused bound pass
    window_secs: float        # wall of one bound-less megastep window
    overhead_pct_at_pick: float


_bound_cadence_cache: dict = {}


def _bound_cadence_disk_lookup(key):
    dk = _persist_get("bound_cadence", repr(key))
    if dk is None:
        return None
    _metrics.inc("tune.disk_hits")
    res = BoundCadenceTune(
        every=int(dk["every"]), bound_secs=float(dk["bound_secs"]),
        window_secs=float(dk["window_secs"]),
        overhead_pct_at_pick=float(dk["overhead_pct_at_pick"]))
    _bound_cadence_cache[key] = res
    return res


def bound_cadence_verdict(shape, settings=None) -> int | None:
    """Banked bound-pass cadence for a shape (None = no verdict — the
    hub then runs the pass every window).  ``shape`` is one (S, n, m)
    triple or the bucketed tuple-of-triples, like
    :func:`megastep_verdict`."""
    key = _mega_key(shape, settings)
    hit = _bound_cadence_cache.get(key) or _bound_cadence_disk_lookup(key)
    return hit.every if hit is not None else None


def autotune_bound_cadence(run_window, shape, settings=None,
                           target_pct: float = 10.0, every_cap: int = 8,
                           cache: bool = True):
    """Measure the marginal cost of the in-wheel bound pass and pick the
    smallest cadence k keeping it under ``target_pct`` percent of the
    wheel wall (bound_secs / (k*window_secs + bound_secs) <= f).

    ``run_window(bound_live)`` executes ONE real megastep window end to
    end (dispatch + packed fetch, measurement applied normally — warmup
    work is never wasted, the autotune_megastep posture) and returns the
    executed iteration count.  Three windows run: a compile-absorbing
    bound-pass warmup, a timed bound-pass window, a timed plain window.
    k=1 (every window) wins whenever the pass is cheap — the common case,
    since the frozen evaluation re-enters the window's still-hot factors.
    Degenerate probes (a converged or rejected window) return the
    conservative every-window answer WITHOUT banking.
    """
    key = _mega_key(shape, settings)
    if cache:
        hit = (_bound_cadence_cache.get(key)
               or _bound_cadence_disk_lookup(key))
        if hit is not None:
            return hit
    run_window(True)                    # compile-absorbing warmup
    t0 = time.time()
    ex_b = int(run_window(True))
    t_bound = time.time() - t0
    t0 = time.time()
    ex_p = int(run_window(False))
    t_plain = time.time() - t0
    if ex_b < 1 or ex_p < 1:
        _probe_event("bound_cadence", {"shape": repr(shape),
                                       "skipped": "degenerate probe",
                                       "executed": (ex_b, ex_p)})
        return BoundCadenceTune(every=1, bound_secs=max(t_bound, 0.0),
                                window_secs=max(t_plain, 1e-9),
                                overhead_pct_at_pick=100.0)
    # normalize to per-iteration so unequal executed counts don't skew
    # the marginal-cost estimate: t_bound = ex_b*c + B with c =
    # t_plain/ex_p, so B = ex_b * (t_bound/ex_b - t_plain/ex_p) — the
    # multiplier is the BOUND window's executed count (the pass ran once
    # in THAT window), not the plain window's
    bound_secs = max(t_bound / ex_b - t_plain / ex_p, 0.0) * ex_b
    window_secs = max(t_plain, 1e-9)
    f = max(target_pct, 1e-3) / 100.0
    k = int(np.ceil(bound_secs * (1.0 - f) / (f * window_secs))) \
        if bound_secs > 0 else 1
    k = max(1, min(k, max(1, int(every_cap))))
    pct = 100.0 * bound_secs / (bound_secs + k * window_secs)
    res = BoundCadenceTune(every=k, bound_secs=bound_secs,
                           window_secs=window_secs,
                           overhead_pct_at_pick=pct)
    _probe_event("bound_cadence", {"shape": repr(shape), "pick": k,
                                   "bound_secs": bound_secs,
                                   "window_secs": window_secs,
                                   "overhead_pct_at_pick": pct})
    if cache:
        _bound_cadence_cache[key] = res
        _persist_put("bound_cadence", repr(key), {
            "every": int(k), "bound_secs": float(bound_secs),
            "window_secs": float(window_secs),
            "overhead_pct_at_pick": float(pct)})
    return res


# ---------------------------------------------------------------------------
# Integer stage (batched integer wheel, doc/integer.md): pick the rounding
# sweep width K (how many ladder thresholds the integer bound pass
# evaluates on device — the SLAM slams always ride) and the pass cadence
# from the MEASURED marginal sweep cost vs the plain window wall.  A wide
# ladder finds integer-feasible incumbents sooner (best-of-C); each extra
# candidate costs one more vmapped frozen evaluation per pass.  Verdicts
# persist under the "integer" kind on the same shape+settings key family
# as the megastep/bound-cadence stages.
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class IntegerTune:
    k: int                    # picked ladder width (thresholds evaluated)
    every: int                # integer pass every k-th megastep window
    sweep_secs: float         # marginal cost of one full integer pass
    window_secs: float        # wall of one bound-less megastep window


_integer_cache: dict = {}


def _integer_disk_lookup(key):
    dk = _persist_get("integer", repr(key))
    if dk is None:
        return None
    _metrics.inc("tune.disk_hits")
    res = IntegerTune(
        k=int(dk["k"]), every=int(dk["every"]),
        sweep_secs=float(dk["sweep_secs"]),
        window_secs=float(dk["window_secs"]))
    _integer_cache[key] = res
    return res


def integer_verdict(shape, settings=None) -> IntegerTune | None:
    """Banked integer-sweep verdict for a shape (None = no verdict — the
    hub then runs the default ladder every bound window).  ``shape`` is
    one (S, n, m) triple or the bucketed tuple-of-triples, like
    :func:`megastep_verdict`."""
    key = _mega_key(shape, settings)
    return _integer_cache.get(key) or _integer_disk_lookup(key)


def autotune_integer(run_window, shape, settings=None, k_full: int = 3,
                     target_pct: float = 15.0, every_cap: int = 8,
                     cache: bool = True):
    """Measure the marginal cost of the batched integer bound pass and
    pick (K, cadence) keeping it under ``target_pct`` percent of the
    wheel wall.

    ``run_window(int_live)`` executes ONE real megastep window end to end
    (dispatch + packed fetch, measurement applied normally — warmup work
    is never wasted, the autotune_megastep posture) with the integer
    bound pass on (True) or off (False), returning the executed
    iteration count.  Three windows run: a compile-absorbing integer
    warmup, a timed integer window, a timed plain window.  The sweep
    cost scales ~linearly in the candidate count (C = K + 2 slams), so
    K shrinks first (never below 1 — the nearest-rounding candidate
    always rides) and the cadence stretches only when K=1 still misses
    the target.  Degenerate probes (a converged or rejected window)
    return the conservative full-ladder answer WITHOUT banking.
    """
    key = _mega_key(shape, settings)
    if cache:
        hit = _integer_cache.get(key) or _integer_disk_lookup(key)
        if hit is not None:
            return hit
    k_full = max(1, int(k_full))
    run_window(True)                    # compile-absorbing warmup
    t0 = time.time()
    ex_i = int(run_window(True))
    t_int = time.time() - t0
    t0 = time.time()
    ex_p = int(run_window(False))
    t_plain = time.time() - t0
    if ex_i < 1 or ex_p < 1:
        _probe_event("integer", {"shape": repr(shape),
                                 "skipped": "degenerate probe",
                                 "executed": (ex_i, ex_p)})
        return IntegerTune(k=k_full, every=1,
                           sweep_secs=max(t_int, 0.0),
                           window_secs=max(t_plain, 1e-9))
    # per-iteration normalization (the bound_cadence estimator): the
    # pass ran once in the integer window
    sweep_secs = max(t_int / ex_i - t_plain / ex_p, 0.0) * ex_i
    window_secs = max(t_plain, 1e-9)
    f = max(target_pct, 1e-3) / 100.0
    # cost model: sweep_secs covers C_full = k_full + 2 evaluations + the
    # reduced-cost re-solve; per-evaluation cost is ~sweep/(C_full + 1)
    per_eval = sweep_secs / max(k_full + 3, 1)
    k = k_full
    every = 1
    while k > 1 and (k + 3) * per_eval > f * window_secs:
        k -= 1
    if (k + 3) * per_eval > f * window_secs:
        cost = (k + 3) * per_eval
        every = int(np.ceil(cost * (1.0 - f) / (f * window_secs)))
        every = max(1, min(every, max(1, int(every_cap))))
    res = IntegerTune(k=k, every=every, sweep_secs=sweep_secs,
                      window_secs=window_secs)
    _probe_event("integer", {"shape": repr(shape), "k": k, "every": every,
                             "sweep_secs": sweep_secs,
                             "window_secs": window_secs})
    if cache:
        _integer_cache[key] = res
        _persist_put("integer", repr(key), {
            "k": int(k), "every": int(every),
            "sweep_secs": float(sweep_secs),
            "window_secs": float(window_secs)})
    return res


# ---------------------------------------------------------------------------
# Batched stage (continuous batching, doc/serving.md "Continuous
# batching"): pick the tenant-batched megastep's slot count K from the
# MEASURED per-window cost.  One fused window runs every live slot's
# frozen sweep back to back, so window wall grows ~linearly in K; the
# verdict is the largest K whose modeled window wall stays inside
# ``target_frac`` of the dispatch watchdog budget — the same budget the
# static cap (segmented.megastep_cap_multi at K copies of the shape)
# guards a priori, but measured, so a fast family batches wider than the
# worst-case flop model would dare.  Verdicts persist under the
# "batched" kind on the same shape+settings key family.
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class BatchedTune:
    k: int                    # picked slot count
    per_slot_secs: float      # marginal window cost per live slot
    base_secs: float          # window wall at one live slot
    window_secs_at_k: float   # modeled window wall at the pick


_batched_cache: dict = {}


def _batched_disk_lookup(key):
    dk = _persist_get("batched", repr(key))
    if dk is None:
        return None
    _metrics.inc("tune.disk_hits")
    res = BatchedTune(
        k=int(dk["k"]), per_slot_secs=float(dk["per_slot_secs"]),
        base_secs=float(dk["base_secs"]),
        window_secs_at_k=float(dk["window_secs_at_k"]))
    _batched_cache[key] = res
    return res


def batched_verdict(S, n=None, m=None, settings=None) -> int | None:
    """Banked autotuned slot count for a family shape (None = no verdict
    — the server then runs its configured ``batch_slots``).  ``S`` may
    be the full shape key, like :func:`megastep_verdict`."""
    shape = (S, n, m) if n is not None else S
    key = _mega_key(shape, settings)
    hit = _batched_cache.get(key) or _batched_disk_lookup(key)
    return hit.k if hit is not None else None


def autotune_batched(run_window, shape, k_cap, target_frac: float = 0.5,
                     k_probe: int | None = None, cache: bool = True,
                     settings=None, target_secs: float | None = None):
    """Measure the fused tenant window's per-slot marginal cost and pick
    the max K whose modeled window wall ``base + (K-1) * per_slot`` stays
    under ``target_frac`` of the dispatch watchdog budget, clamped to
    ``k_cap``.

    ``run_window(k)`` executes ONE fused window with ``k`` live slots
    end to end (dispatch + packed fetch) and returns the executed
    iteration count of its busiest slot.  Probe windows are REAL wheel
    work (the autotune_megastep posture — callers apply each window's
    measurements normally).  Three windows run: a compile-absorbing
    k=1 warmup, a timed k=1, and a timed ``k_probe``; a degenerate probe
    (nothing executed) returns the conservative K=1 WITHOUT banking.
    """
    from .solvers.segmented import _DISPATCH_TARGET_SECS

    key = _mega_key(shape, settings)
    if cache:
        hit = _batched_cache.get(key) or _batched_disk_lookup(key)
        if hit is not None:
            return hit

    k_cap = max(1, int(k_cap))
    if k_probe is None:
        k_probe = max(2, min(k_cap, 4))
    k_probe = max(2, min(int(k_probe), max(2, k_cap)))
    budget = (target_secs if target_secs is not None
              else max(target_frac, 1e-3) * _DISPATCH_TARGET_SECS)
    run_window(1)                       # compile-absorbing warmup window
    t0 = time.time()
    ex1 = int(run_window(1))
    t1 = time.time() - t0               # one-slot window wall
    t0 = time.time()
    exK = int(run_window(k_probe))
    tK = time.time() - t0               # k_probe-slot window wall
    if ex1 <= 0 or exK <= 0:
        _probe_event("batched", {"shape": repr(shape),
                                 "skipped": "degenerate probe",
                                 "executed": (ex1, exK)})
        return BatchedTune(k=1, per_slot_secs=max(tK, 1e-9),
                           base_secs=max(t1, 1e-9),
                           window_secs_at_k=max(t1, 1e-9))
    per_slot = max((tK - t1) / max(k_probe - 1, 1), 1e-9)
    base = max(t1, 1e-9)
    k = int((budget - base) // per_slot) + 1 if budget > base else 1
    k = max(1, min(k, k_cap))
    at_k = base + (k - 1) * per_slot
    res = BatchedTune(k=k, per_slot_secs=per_slot, base_secs=base,
                      window_secs_at_k=at_k)
    _probe_event("batched", {"shape": repr(shape), "pick": k,
                             "per_slot_secs": per_slot,
                             "base_secs": base,
                             "window_secs_at_k": at_k})
    if cache:
        _batched_cache[key] = res
        _persist_put("batched", repr(key), {
            "k": int(k), "per_slot_secs": float(per_slot),
            "base_secs": float(base),
            "window_secs_at_k": float(at_k)})
    return res
