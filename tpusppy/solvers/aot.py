"""AOT executable cache: serialized XLA programs, content-addressed on disk.

ROADMAP item 3 ("kill the cold start").  The hot jitted programs — the
fused PH step and wheel megakernel (:mod:`tpusppy.parallel.sharded`), the
frozen/refresh batch solves behind ``spopt._solve_amortized``
(:mod:`.admm` / :mod:`.shared_admm`), and the packed-measurement/stats
programs — are compiled once per (shape, settings, mesh, toolchain) and
then recompiled from scratch by EVERY process that touches them: every
resume, every ladder rung, every ``dist_wheel`` controller pays the full
XLA lower+compile again (UC ~17 s, farmer ~3.5 s per process —
BENCH_r06 ``compile_iter0_s``).  This module persists the compiled
executables themselves (``jax.jit(...).lower().compile()`` serialized via
:mod:`jax.experimental.serialize_executable`) in a content-addressed
on-disk cache, so a repeated, resumed, or ladder-sibling run skips XLA
entirely and reaches its first PH iteration in milliseconds.

Usage: wrap a jitted function once at build time::

    fused = aot.cached_program(fused, "ph_fused", key_extra=(settings, ...))

The wrapper is a strict passthrough while the cache is disarmed (no
``TPUSPPY_AOT_CACHE`` / :func:`set_cache_path`) or when called under a
trace (nested jit), so cold-path behavior is bitwise-identical to the
plain jitted call.  Armed, each call signature (leaf avals + static
kwargs + ``key_extra`` + jax/jaxlib/platform) maps to one key; the first
call either deserializes ``<dir>/<key>.aotx`` ("aot.load" span,
``aot.hits``) or lower+compiles ("aot.compile" span, ``aot.misses``) and
serializes the result atomically.  Donation semantics ride the
executable (a loaded program donates exactly like its jit twin — tests
pin this).

Keying: the cache key hashes the SAME shape+settings+mesh parts the
autotuner's verdict store uses (:func:`family_parts` — tune's key builder
delegates here so the two caches can never silently drift), the
program-specific extras, and the toolchain fingerprint (jax + jaxlib
versions, backend platform, device count).  A toolchain bump therefore
changes every key — old files are simply never read again (and a
belt-and-braces in-file version guard rejects foreign payloads that were
renamed into place).  Corrupted/truncated files deserialize-fail into a
clean miss-and-recompile, never a crash and never a stale hit.

Fallback tier: arming this cache also arms JAX's persistent compilation
cache at :func:`compile_cache_dir` — ``$JAX_COMPILATION_CACHE_DIR`` when
the caller placed one, else ``<checkout>/.jax_cache`` — so programs
nobody explicitly wrapped still compile warm from the disk cache (they
re-pay tracing, not XLA).  That function is the ONLY place the program
names a compile-cache directory; a fixed path matters because the path
is part of JAX's cache key (a directory that moves never hits).

Scope: single-controller processes only (``jax.process_count() == 1``) —
a multi-controller mesh's executables embed global device assignments
this loader does not reconstruct.  See doc/autotuner.md ("Cold start")
and doc/observability.md for the ``aot.*`` counter names.
"""

from __future__ import annotations

import contextlib
import hashlib
import os
import pickle
import re
import tempfile
import threading
import time

import numpy as np

from ..obs import metrics as _metrics
from ..obs import trace as _trace
from ..obs.log import get_logger

_log = get_logger("aot")

#: In-file payload format version (independent of the key hash — guards
#: files renamed/copied into place from a foreign build).
_FORMAT_VERSION = 2

#: Cap for :func:`prewarm` with ``keys=None`` (newest-first): loading a
#: whole long-lived cache directory eagerly would burn startup time on
#: programs this process may never call.
PREWARM_MAX_FILES = 64

_CTR_HITS = _metrics.counter("aot.hits")
_CTR_MISSES = _metrics.counter("aot.misses")
_CTR_LOAD_ERRORS = _metrics.counter("aot.load_errors")
_CTR_SERIALIZE_ERRORS = _metrics.counter("aot.serialize_errors")
_CTR_UNSERIALIZABLE = _metrics.counter("aot.unserializable")
_CTR_QUARANTINED = _metrics.counter("aot.quarantined")
_CTR_PREWARMED = _metrics.counter("aot.prewarmed")
_CTR_FROM_JAX_CACHE = _metrics.counter("aot.from_jax_cache")
_HIST_COMPILE_S = _metrics.histogram("aot.compile_s")
_HIST_SERIALIZE_S = _metrics.histogram("aot.serialize_s")
_HIST_DESERIALIZE_S = _metrics.histogram("aot.deserialize_s")

_lock = threading.Lock()
# ONE process-wide lock around every deserialize AND aot-initiated
# compile: this jaxlib's XLA:CPU `deserialize_executable` races in-flight
# compilation (observed as "INTERNAL: Symbols not found" in one
# interleaving and a hard segfault in another, reproduced under the
# 3-cylinder wheel's concurrent warm start).  Serializing aot's own XLA
# work removes the aot-vs-aot interleavings; the wheel spinner closes the
# remaining aot-load-vs-plain-jit-compile window by prewarming the cache
# BEFORE its cylinder threads start.
_xla_work_lock = threading.RLock()
_cache_path_override: str | None = None
_loaded: dict = {}            # key -> loaded jax Compiled
_session_keys: list = []      # keys compiled-or-loaded, insertion order


# ---------------------------------------------------------------------------
# Cache location (the tune-cache scoping discipline: programmatic override
# first, then the env knob; tests use set_cache_path so no env leaks).
# ---------------------------------------------------------------------------
def set_cache_path(path: str | None):
    """Programmatic override of the TPUSPPY_AOT_CACHE knob — scoped to
    this process, the same contract as :func:`tpusppy.tune.set_cache_path`
    (tests must never leak cache state via env vars)."""
    global _cache_path_override
    _cache_path_override = str(path) if path else None


def cache_path() -> str | None:
    """The armed executable-cache DIRECTORY (programmatic override first,
    then ``TPUSPPY_AOT_CACHE``; empty/unset disables the cache entirely —
    every wrapped program then calls its plain jit twin)."""
    return (_cache_path_override
            or os.environ.get("TPUSPPY_AOT_CACHE") or None)


def enabled() -> bool:
    """Cache armed AND usable from this process (single-controller only:
    multi-controller executables embed global device assignments)."""
    if cache_path() is None:
        return False
    return not _multiprocess()


_multiprocess_memo: bool | None = None


def _multiprocess() -> bool:
    # memoized: enabled() sits on every wrapped call, and process count
    # never changes after backend init (reset() clears the memo)
    global _multiprocess_memo
    if _multiprocess_memo is None:
        try:
            import jax

            _multiprocess_memo = jax.process_count() > 1
        except Exception:
            return False
    return _multiprocess_memo


def reset():
    """Drop every in-memory executable and the path override (test
    isolation; on-disk files are untouched)."""
    global _cache_path_override, _multiprocess_memo
    with _lock:
        _loaded.clear()
        _session_keys.clear()
    _cache_path_override = None
    _multiprocess_memo = None


_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def compile_cache_dir() -> str:
    """Where JAX's persistent compilation cache lives for this program:
    ``$JAX_COMPILATION_CACHE_DIR`` when the caller placed it, else the
    fixed in-checkout ``.jax_cache`` (git-ignored)."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(_CHECKOUT, ".jax_cache"))


def arm_compile_cache() -> str:
    """Point JAX's persistent compilation cache at
    :func:`compile_cache_dir` and export it so child processes share the
    directory.  Idempotent; every entry point that wants warm compiles
    (the AOT fallback tier, the wheel spinner, bench, the test suite)
    calls this and nothing else."""
    import jax

    d = compile_cache_dir()
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", d)
    if jax.config.jax_compilation_cache_dir != d:
        jax.config.update("jax_compilation_cache_dir", d)
    return d


# ---------------------------------------------------------------------------
# Executables that came out of JAX's persistent cache must not be
# re-serialized on XLA:CPU: `serialize` of such an executable yields a
# smaller artifact that LOADS in the next process and then fails at
# execute ("Function wrapped_add not found"; reproduced on jaxlib 0.9.0 by
# scripts/aot_cache_origin_probe.py).  JAX reports a persistent-cache hit
# synchronously on the compiling thread, so a thread-local tally taken
# around our own compile attributes the hit to exactly that compile — other
# cylinder threads compiling at the same time never touch this thread's
# count, and no process-global flag is toggled.
# ---------------------------------------------------------------------------
_tls = threading.local()
_listener_registered = False


def _on_jax_event(event, **_kw):
    if event == "/jax/compilation_cache/cache_hits":
        _tls.cache_hits = getattr(_tls, "cache_hits", 0) + 1


def _thread_cache_hits() -> int:
    global _listener_registered
    if not _listener_registered:
        with _lock:
            if not _listener_registered:
                import jax.monitoring

                jax.monitoring.register_event_listener(_on_jax_event)
                _listener_registered = True
    return getattr(_tls, "cache_hits", 0)


# ---------------------------------------------------------------------------
# Keys.  family_parts is THE shared shape+settings+mesh key builder: the
# autotuner's verdict keys (tune._tune_key) start with exactly this tuple,
# so tune-cache keys and executable-cache keys cannot silently drift.
# ---------------------------------------------------------------------------
def family_parts(arr, settings, mesh, axis) -> tuple:
    """(c.shape, cl.shape, A-kind, settings, n_devices, axis) — the common
    prefix of every cache key derived from one problem family."""
    ndev = 1 if mesh is None else len(mesh.devices.flat)
    return (arr.c.shape, arr.cl.shape,
            arr.A.ndim if hasattr(arr.A, "ndim") else "sparse",
            settings, ndev, axis)


def shape_family_parts(S, n, m, settings=None, a_kind="?", ndev=1,
                       axis="scen") -> tuple:
    """:func:`family_parts` for callers that know only the (S, n, m)
    shape — SAME tuple structure and field order, so keys built from a
    bare shape (the tune megastep verdicts) can never silently drift
    from keys built from real arrays (drift guard in tests/test_tune).
    ``a_kind`` stays the wildcard ``"?"`` when the engine is not part of
    the caller's identity."""
    return ((int(S), int(n)), (int(S), int(m)), a_kind, settings,
            int(ndev), axis)


def _versions() -> tuple:
    """Toolchain fingerprint every key embeds: executable serialization is
    where jax/jaxlib drift bites first, and a deserialized program must
    only ever run on the toolchain+backend that built it."""
    try:
        import jax
        import jaxlib

        plat = "?"
        with contextlib.suppress(Exception):
            plat = jax.devices()[0].platform
        return (str(jax.__version__), str(jaxlib.__version__), plat)
    except ImportError:
        return ("none", "none", "none")


def mesh_fingerprint(mesh) -> tuple | None:
    """Key part for a mesh: axis names + shape (device COUNT rides the
    toolchain fingerprint's platform and the executable's own device
    assignment)."""
    if mesh is None:
        return None
    return (tuple(mesh.axis_names), tuple(mesh.devices.shape))


def array_digest(a) -> str:
    """Digest of a small host array baked into a program as a constant
    (e.g. ``nonant_idx``): programs differing only in such constants MUST
    key differently."""
    a = np.ascontiguousarray(np.asarray(a))
    return hashlib.sha1(
        repr((a.shape, str(a.dtype))).encode() + a.tobytes()).hexdigest()


def _leaf_sig(leaf):
    from jax.api_util import shaped_abstractify

    aval = shaped_abstractify(leaf)
    return (tuple(aval.shape), str(aval.dtype),
            bool(getattr(aval, "weak_type", False)))


def program_key(kind: str, sig, key_extra) -> str:
    """``<kind>.<digest>`` — the cache filename stem.  ``sig`` is the
    call-signature tuple (treedef + leaf avals), ``key_extra`` the
    build-time identity (settings, cadence, constant digests, ...)."""
    blob = repr((kind, sig, key_extra, _versions())).encode()
    return f"{kind}.{hashlib.sha1(blob).hexdigest()[:20]}"


# ---------------------------------------------------------------------------
# Serialization safety.  XLA:CPU custom-call targets that reference
# runtime symbols by RAW POINTER (the LAPACK FFI kernels — potrf/getrf/
# trsm behind cholesky/lu/triangular_solve) do NOT survive cross-process
# executable deserialization on this toolchain: loading them in a fresh
# process segfaults (reproduced: a jitted `jnp.linalg.cholesky` roundtrip
# dies; pure matmul/while_loop programs — the frozen sweeps, the wheel
# megastep, the packed measurements — roundtrip bit-exact).  So a program
# whose LOWERED module carries any custom_call target outside the
# by-value allowlist below is compiled and used in-memory but NEVER
# persisted (``aot.unserializable``); its recompiles ride the jax
# persistent-compilation-cache fallback tier instead, which handles these
# kernels correctly.  On TPU, cholesky lowers natively (no LAPACK custom
# call), so the adaptive/refresh programs persist there — exactly where
# the UC ~17 s cold start lives.
# ---------------------------------------------------------------------------
#: Custom-call targets serialized BY VALUE (payload/attribute-carried),
#: safe to persist: sharding markers and the Pallas/Mosaic TPU kernels.
SAFE_CUSTOM_CALLS = frozenset({
    "Sharding", "SPMDFullToShardShape", "SPMDShardToFullShape",
    "shape_assertion", "annotate_device_placement", "tpu_custom_call",
})

# all three spellings a custom call prints under: pretty stablehlo
# (`custom_call @target`), the generic MLIR attribute form
# (`call_target_name = "target"`), and classic HLO text
# (`custom_call_target="target"`) — missing one would classify a LAPACK
# program serialize-safe and persist an artifact that segfaults the next
# process's load
_CUSTOM_CALL_RE = re.compile(
    r'custom_call\s+@([\w.$-]+)'
    r'|custom_call_target\s*=\s*"([^"]+)"'
    r'|call_target_name\s*=\s*"([^"]+)"')


def _custom_call_targets(lowered_text: str) -> set:
    return {a or b or c for a, b, c in _CUSTOM_CALL_RE.findall(lowered_text)}


def serialize_safe(lowered) -> tuple[bool, set]:
    """(safe, offending-targets) for one lowered program."""
    try:
        targets = _custom_call_targets(lowered.as_text())
    except Exception:
        return False, set()
    unsafe = targets - SAFE_CUSTOM_CALLS
    return not unsafe, unsafe


# ---------------------------------------------------------------------------
# Disk format: pickle of {"v", "jax", "jaxlib", "platform", "devices",
# "payload"} where payload is
# jax.experimental.serialize_executable.serialize(...) and devices the ids
# the program was compiled for, in assignment order (the loader needs them:
# without ``execution_devices`` a one-device program is loaded onto every
# device of the backend and fails at its first call).
# Writes are atomic (tempfile + os.replace) so a kill mid-write can never
# leave a torn file; a torn/foreign file is just a cold cache.
# ---------------------------------------------------------------------------
def _entry_path(key: str) -> str:
    return os.path.join(cache_path(), key + ".aotx")


def _quarantine_path(key: str) -> str:
    """Marker for keys whose artifact FAILED to load once: this
    toolchain's CPU executable loader deterministically refuses some
    artifacts (symbol-name drift when the serializing process had
    compiled other programs first — "Symbols not found"), and a
    re-serialized replacement from the same process is usually just as
    unloadable.  The marker stops the probe/fail/rewrite churn: the key
    lives on the jax-cache fallback tier until a toolchain bump renames
    it (keys embed the versions)."""
    return os.path.join(cache_path(), key + ".aotx.bad")


def _atomic_write_bytes(path: str, blob: bytes):
    d = os.path.dirname(os.path.abspath(path)) or "."
    os.makedirs(d, exist_ok=True)
    # suffix must NOT be ".aotx": prewarm's directory sweep would treat a
    # concurrent writer's half-written temp file as a real entry, fail to
    # load it, delete it out from under the writer and quarantine junk
    fd, tmp = tempfile.mkstemp(prefix=".aot_tmp_", suffix=".tmp", dir=d)
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(blob)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except BaseException:
        with contextlib.suppress(OSError):
            os.remove(tmp)
        raise


def _write_index_entry(key: str, kind: str):
    """Best-effort human-readable sidecar (one ``index.json`` per cache
    dir) via the engine-wide atomic-JSON helper — inspection + debugging,
    never read on the hot path.  Last-writer-wins across processes, like
    the tune cache."""
    try:
        from ..resilience.checkpoint import atomic_write_json

        path = os.path.join(cache_path(), "index.json")
        idx = {}
        if os.path.exists(path):
            import json

            with contextlib.suppress(OSError, ValueError):
                with open(path) as f:
                    idx = json.load(f)
        jv, jlv, plat = _versions()
        idx[key] = {"kind": kind, "jax": jv, "jaxlib": jlv,
                    "platform": plat, "created": time.time()}
        atomic_write_json(path, idx)
    except Exception:            # the index is advisory only
        pass


def _serialize_to_disk(key: str, kind: str, compiled):
    from jax.experimental import serialize_executable as _se

    if os.path.exists(_quarantine_path(key)):
        _CTR_QUARANTINED.inc(1)
        return
    t0 = time.perf_counter()
    try:
        payload = _se.serialize(compiled)
        jv, jlv, plat = _versions()
        devices = [int(d.id) for d in
                   compiled.runtime_executable().local_devices()]
        blob = pickle.dumps({"v": _FORMAT_VERSION, "jax": jv,
                             "jaxlib": jlv, "platform": plat,
                             "devices": devices, "payload": payload})
        _atomic_write_bytes(_entry_path(key), blob)
    except Exception as e:
        # an unserializable program (or a read-only/full cache dir) must
        # cost nothing but the warm-start: the compiled executable is
        # already in memory and the run proceeds normally
        _CTR_SERIALIZE_ERRORS.inc(1)
        _log.warning("executable serialize failed for %s: %r", key, e)
        return
    _HIST_SERIALIZE_S.add(time.perf_counter() - t0)
    _write_index_entry(key, kind)


def _deserialize_from_disk(key: str):
    """Loaded executable, or None on ANY failure (missing, torn,
    truncated, foreign toolchain) — a clean miss, never a crash."""
    path = _entry_path(key)
    if not os.path.exists(path):
        return None
    if os.path.exists(_quarantine_path(key)):
        _CTR_QUARANTINED.inc(1)
        return None
    from jax.experimental import serialize_executable as _se

    t0 = time.perf_counter()
    try:
        with open(path, "rb") as f:
            blob = f.read()
    except OSError:
        # transient read failure (EINTR, permission race, NFS hiccup):
        # a plain miss — the artifact may be perfectly healthy, so it
        # must NOT be deleted or quarantined
        _CTR_LOAD_ERRORS.inc(1)
        return None
    try:
        obj = pickle.loads(blob)
        jv, jlv, plat = _versions()
        if (obj.get("v") != _FORMAT_VERSION or obj.get("jax") != jv
                or obj.get("jaxlib") != jlv or obj.get("platform") != plat):
            # keys embed the toolchain, so this only triggers on files
            # renamed/copied into place — still just a miss
            return None
        import jax

        by_id = {int(d.id): d for d in jax.devices()}
        if any(i not in by_id for i in obj["devices"]):
            # compiled for a device this process does not have (a wider
            # host wrote the entry): a miss, and the artifact stays
            return None
        exe = _se.deserialize_and_load(
            *obj["payload"],
            execution_devices=[by_id[i] for i in obj["devices"]])
    except Exception as e:
        # the ARTIFACT itself is bad (torn pickle, or this toolchain's
        # deterministic "Symbols not found" refusals): quarantine so no
        # process re-pays the failed load or re-banks a twin
        _CTR_LOAD_ERRORS.inc(1)
        _log.warning("executable cache entry %s unreadable (%r) — "
                     "recompiling; key quarantined to the jax-cache "
                     "tier", key, e)
        with contextlib.suppress(OSError):
            os.remove(path)      # don't re-pay the failed read next run
        with contextlib.suppress(OSError):   # see _quarantine_path
            with open(_quarantine_path(key), "w") as f:
                f.write(repr(e)[:500])
        return None
    _HIST_DESERIALIZE_S.add(time.perf_counter() - t0)
    return exe


# ---------------------------------------------------------------------------
# The wrapper.
# ---------------------------------------------------------------------------
class CachedProgram:
    """AOT-cache-aware twin of one jitted function.

    Disabled cache (or a call under an outer trace): a strict passthrough
    to the jitted function.  Enabled: each distinct call signature
    resolves to one serialized executable — deserialized from disk when
    present, else lower+compiled and persisted — and the call dispatches
    the executable directly (no retracing).  Static kwargs
    (``static_names``) join the key and are stripped from the executable
    call, matching ``Compiled``'s calling convention.
    """

    __slots__ = ("_jitted", "kind", "_key_extra", "_static_names",
                 "_sig_keys", "_lock")

    def __init__(self, jitted, kind: str, key_extra=(), static_names=()):
        self._jitted = jitted
        self.kind = str(kind)
        self._key_extra = repr(key_extra)
        self._static_names = tuple(static_names)
        self._sig_keys: dict = {}      # sig -> key (memo)
        self._lock = threading.Lock()

    def __call__(self, *args, **kwargs):
        if not enabled():
            return self._jitted(*args, **kwargs)
        statics = {k: kwargs[k] for k in self._static_names if k in kwargs}
        dyn_kwargs = {k: v for k, v in kwargs.items() if k not in statics}
        import jax

        leaves, treedef = jax.tree_util.tree_flatten((args, dyn_kwargs))
        # FAST dispatch memo: this wrapper sits on the steady-state hot
        # path (one frozen solve / megastep per wheel window), so the
        # per-call key must not pay shaped_abstractify + str(treedef) +
        # static reprs every time.  The memo key uses cheap hashables —
        # jax Arrays' cached .aval, numpy metadata, python scalar types,
        # and the (frozen, value-hashable) static objects themselves —
        # and is at least as discriminating as the canonical signature,
        # which is still what the on-disk key digests (memo-miss path),
        # so cross-process keys stay deterministic.
        try:
            metas = []
            for leaf in leaves:
                if isinstance(leaf, jax.core.Tracer):
                    # nested under an outer trace: inline like jit
                    return self._jitted(*args, **kwargs)
                if isinstance(leaf, jax.Array):
                    metas.append(leaf.aval)
                elif isinstance(leaf, np.ndarray):
                    metas.append(("np", leaf.shape, leaf.dtype.str))
                else:
                    metas.append(("py", type(leaf)))
            memo_key = (treedef, tuple(metas),
                        tuple(sorted(statics.items())))
            key = self._sig_keys.get(memo_key)
        except Exception:
            # unhashable static / exotic leaf: never block the solve
            # over a cache key
            return self._jitted(*args, **kwargs)
        if key is None:
            try:
                sig = (str(treedef),
                       tuple(_leaf_sig(leaf) for leaf in leaves),
                       tuple(sorted((k, repr(v))
                                    for k, v in statics.items())))
            except Exception:
                return self._jitted(*args, **kwargs)
            key = program_key(self.kind, sig, self._key_extra)
            self._sig_keys[memo_key] = key
        exe = _loaded.get(key)
        if exe is None:
            exe = self._resolve(key, args, kwargs)
        return exe(*args, **dyn_kwargs)

    def _resolve(self, key: str, args, kwargs):
        with self._lock:
            exe = _loaded.get(key)
            if exe is not None:
                return exe
            arm_compile_cache()
            with _xla_work_lock, _trace.span("compile", "aot.load"):
                exe = _deserialize_from_disk(key)
            if exe is not None:
                _CTR_HITS.inc(1)
                if _trace.enabled():
                    _trace.instant("compile", "aot.hit", key=key,
                                   kind=self.kind)
            else:
                _CTR_MISSES.inc(1)
                t0 = time.perf_counter()
                with _xla_work_lock, \
                        _trace.span("compile", "aot.compile") as _sp:
                    lowered = self._jitted.lower(*args, **kwargs)
                    safe, offending = serialize_safe(lowered)
                    hits0 = _thread_cache_hits()
                    exe = lowered.compile()
                    from_jax_cache = _thread_cache_hits() > hits0
                    if _trace.enabled():
                        _sp.add(key=key, kind=self.kind)
                _HIST_COMPILE_S.add(time.perf_counter() - t0)
                if safe and from_jax_cache and _versions()[2] == "cpu":
                    # see _thread_cache_hits: the artifact would load and
                    # then fail at execute; the jax cache already holds it
                    _CTR_FROM_JAX_CACHE.inc(1)
                elif safe:
                    _serialize_to_disk(key, self.kind, exe)
                else:
                    # by-pointer custom calls (see SAFE_CUSTOM_CALLS):
                    # persisting would segfault the NEXT process's load —
                    # leave this program to the jax-cache fallback tier
                    _CTR_UNSERIALIZABLE.inc(1)
                    _log.info(
                        "%s not persisted (by-pointer custom calls: %s) — "
                        "recompiles ride the jax compilation cache",
                        key, sorted(offending) or "unscannable")
            with _lock:
                _loaded[key] = exe
                _session_keys.append(key)
            return exe


def cached_program(jitted, kind: str, key_extra=(), static_names=()):
    """Wrap a jitted function with the executable cache (see
    :class:`CachedProgram`).  ``key_extra`` must carry everything baked
    into the program that the call signature doesn't show: settings,
    cadence/chunk knobs, closure constants (via :func:`array_digest`),
    the mesh (:func:`mesh_fingerprint`)."""
    return CachedProgram(jitted, kind, key_extra=key_extra,
                         static_names=static_names)


# ---------------------------------------------------------------------------
# Prewarm: deserialize executables into memory BEFORE first use — the
# wheel spinner's pre-thread preload, tune.prewarm_aot's pre-iter0 load,
# and the resume path after a checkpoint hands over its cache pointer.
# SYNCHRONOUS callers are the norm: the loader is only reliable while no
# compile is in flight (see _xla_work_lock), so front-loading beats
# overlapping.
# ---------------------------------------------------------------------------
def session_mark() -> int:
    """Position marker into the session key log (pair with
    :func:`session_keys_since` to attribute keys to one tuning call)."""
    with _lock:
        return len(_session_keys)


def session_keys_since(mark: int = 0) -> list:
    """Keys compiled-or-loaded by this process since ``mark``."""
    with _lock:
        return list(_session_keys[int(mark):])


def prewarm(keys=None) -> int:
    """Synchronously deserialize cached executables into memory; returns
    how many loaded.  ``keys=None`` loads the newest
    :data:`PREWARM_MAX_FILES` entries in the cache dir.  Unknown keys and
    unreadable files are skipped silently (they will resolve — or
    recompile — on first call).

    Trade-off note: the directory sweep cannot know which entries this
    run will call, so against a long-lived shared cache dir it may load
    programs of other shape families — bounded by the cap at a few
    seconds of startup and their resident memory, the price of the warm
    start for runs (wheels without banked tune verdicts) whose keys
    nothing recorded.  Prewarmed loads count into ``aot.prewarmed`` AND
    ``aot.hits``, in whatever metrics window the prewarm ran."""
    if not enabled():
        return 0
    d = cache_path()
    if keys is None:
        def _mtime(nm):
            # a sibling process may delete entries (quarantine/wipe)
            # between listdir and here — a vanished file sorts oldest,
            # it must never crash the sweep
            try:
                return os.path.getmtime(os.path.join(d, nm))
            except OSError:
                return 0.0

        try:
            names = [nm for nm in os.listdir(d) if nm.endswith(".aotx")]
            # sweep orphaned atomic-write temp files (a SIGKILL mid-
            # serialize strands one; nothing else ever looks at them) —
            # age-guarded so a LIVE writer's in-flight temp survives
            for nm in os.listdir(d):
                if nm.startswith(".aot_tmp_") and nm.endswith(".tmp"):
                    p = os.path.join(d, nm)
                    with contextlib.suppress(OSError):
                        if time.time() - os.path.getmtime(p) > 3600.0:
                            os.remove(p)
        except OSError:
            return 0
        names.sort(key=_mtime, reverse=True)
        keys = [nm[:-len(".aotx")] for nm in names[:PREWARM_MAX_FILES]]
    n = 0
    for key in keys:
        with _lock:
            if key in _loaded:
                continue
        with _xla_work_lock, _trace.span("compile", "aot.load"):
            exe = _deserialize_from_disk(str(key))
        if exe is None:
            continue
        with _lock:
            if key not in _loaded:
                _loaded[key] = exe
                _session_keys.append(key)
                n += 1
    if n:
        _CTR_PREWARMED.inc(n)
        _CTR_HITS.inc(n)
        _log.info("prewarmed %d executable(s) from %s", n, d)
    return n


def prewarm_async(keys=None) -> threading.Thread | None:
    """Fire-and-forget :func:`prewarm` on a daemon thread (None when the
    cache is disarmed).  Use ONLY when nothing else will compile while
    the thread runs — a concurrent plain-jit compile can crash the
    loader (see :data:`_xla_work_lock`); the shipped call sites all
    prefer the synchronous :func:`prewarm`."""
    if not enabled():
        return None
    th = threading.Thread(target=prewarm, args=(keys,),
                          name="aot-prewarm", daemon=True)
    th.start()
    return th
