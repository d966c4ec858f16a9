"""Structured trace ring buffer: spans, instants, counters.

The flight-recorder core: a thread-safe bounded ring of
:class:`Event` tuples ``(t, tid, track, name, kind, dur, payload)``.
Recording is OFF by default and the disabled fast path is pinned by a
test: every public record function starts with one module-flag check and
returns a shared singleton (no event tuple, no payload dict is
constructed), so instrumentation can stay in hot paths permanently.

Tracks are logical timelines (one per cylinder / controller /
listener-thread — see doc/observability.md for the naming scheme).  Most
instrumentation passes ``track=None`` which resolves to the calling
thread's track (:func:`set_thread_track` — the wheel spinner names its
cylinder threads); fixed subsystem timelines ("host-sync", "dispatch",
"mailbox", …) pass their track explicitly.  The OS thread ident is
recorded per event so the Perfetto exporter can keep concurrent spans on
one logical track from interleaving their begin/end pairs.

Coarse phases of a cylinder (a dozen per hub iteration at most) go
through :func:`phase` instead of :func:`span`: one call site feeds the
profiler's trace (``jax.profiler.TraceAnnotation``, so the phase lands on
the clock of the device's operations), the always-on registry
(``phase.<cylinder>.<name>.{secs,count}``) and, when enabled, this ring.
This is the only file of the program that names ``jax.profiler``.
What a solve spent under those seconds and how it ended goes through
:func:`outcome`: sums in the registry alone
(``solve.<cylinder>.<kind>.<field>``).

Enablement: ``TPUSPPY_TRACE=<path>`` in the environment turns tracing on
at import and registers an atexit flush of ``<path>`` (Perfetto JSON)
plus ``<path>.report.json`` (the :mod:`.report` summary); programmatic
:func:`enable`/:func:`disable` and :func:`flush` do the same on demand.
``Config.tracing`` (see :meth:`tpusppy.utils.config.Config.tracing_args`)
routes here through :func:`maybe_enable_from_config`.
"""

from __future__ import annotations

import collections
import contextlib
import os
import threading
import time
from typing import NamedTuple

from . import metrics as _metrics

#: Default ring capacity (events).  At the wheel's event rates (~10-100
#: events/iteration) this keeps minutes of history; the ring drops the
#: OLDEST events on overflow (``dropped`` counts them).
DEFAULT_CAPACITY = 131072

_perf = time.perf_counter


class Event(NamedTuple):
    t: float            # perf_counter timestamp (seconds)
    tid: int            # OS thread ident at record time
    track: str          # logical timeline name
    name: str           # event name
    kind: str           # "span" | "instant" | "counter"
    dur: float | None   # span duration (seconds); None otherwise
    payload: dict | None


class TraceBuffer:
    """Thread-safe bounded ring of events (newest kept on overflow)."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self.capacity = int(capacity)
        self._dq: collections.deque = collections.deque(maxlen=self.capacity)
        self._lock = threading.Lock()
        self.dropped = 0

    def add(self, ev: Event):
        with self._lock:
            if len(self._dq) == self.capacity:
                self.dropped += 1
            self._dq.append(ev)

    def snapshot(self) -> list:
        """Copy of the current events, oldest first."""
        with self._lock:
            return list(self._dq)

    def clear(self):
        with self._lock:
            self._dq.clear()
            self.dropped = 0

    def __len__(self):
        with self._lock:
            return len(self._dq)


# ---------------------------------------------------------------------------
# Module state.  `_enabled` is THE fast-path flag: every record function
# checks it first and allocates nothing when False.
# ---------------------------------------------------------------------------
_enabled = False
_buffer = TraceBuffer()
_flush_path: str | None = None
_atexit_registered = False
_tls = threading.local()
# recording generation: bumped by disable()/reset() so a span OPENED in
# an earlier generation (a lingering daemon cylinder thread crossing a
# test fixture's disable+reset+re-enable) drops its event instead of
# leaking it into the next owner's ring
_gen = 0


def enabled() -> bool:
    return _enabled


def set_thread_track(name: str | None):
    """Set (or clear) the calling thread's default track — events recorded
    with ``track=None`` land here.  The wheel spinner names its cylinder
    threads this way ("hub", "spoke1:LagrangianOuterBound", ...)."""
    _tls.track = name


def thread_track() -> str:
    return getattr(_tls, "track", None) or "main"


def cylinder() -> str:
    """The calling thread's cylinder: its track up to the first ``:``
    (``hub``, ``spoke1``, ``spoke2``; ``main`` outside a wheel)."""
    return thread_track().split(":", 1)[0]


def enable(path: str | None = None, capacity: int | None = None):
    """Turn recording on.  ``path`` (optional) arms :func:`flush` and an
    atexit flush; ``capacity`` resizes (and clears) the ring."""
    global _enabled, _flush_path, _buffer, _atexit_registered
    if capacity is not None and capacity != _buffer.capacity:
        _buffer = TraceBuffer(capacity)
    if path:
        _flush_path = str(path)
        if not _atexit_registered:
            import atexit

            atexit.register(_flush_atexit)
            _atexit_registered = True
    _enabled = True


def disable():
    global _enabled, _gen
    _enabled = False
    _gen += 1


def reset(capacity: int | None = None):
    """Clear the ring (recording flag unchanged) — test isolation hook.
    ``capacity`` also restores the ring size (an ``enable(capacity=...)``
    from one owner must not shrink every later owner's ring)."""
    global _gen, _buffer
    _gen += 1
    if capacity is not None and capacity != _buffer.capacity:
        _buffer = TraceBuffer(capacity)
    else:
        _buffer.clear()


def events() -> list:
    """Snapshot of the recorded events, oldest first."""
    return _buffer.snapshot()


def dropped() -> int:
    return _buffer.dropped


# ---------------------------------------------------------------------------
# Recording.  Spans via context manager; `_NULL` is the shared disabled
# singleton (identity-checkable by the overhead test).
# ---------------------------------------------------------------------------
class _NullSpan:
    """Shared no-op span: returned whenever tracing is off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def add(self, **kw):   # payload attach is a no-op too
        pass


_NULL = _NullSpan()


class _Span:
    __slots__ = ("track", "name", "payload", "t0", "gen")

    def __init__(self, track, name, payload):
        self.track = track
        self.name = name
        self.payload = payload

    def __enter__(self):
        self.gen = _gen
        self.t0 = _perf()
        return self

    def add(self, **kw):
        """Attach payload discovered mid-span (recorded at exit)."""
        if self.payload is None:
            self.payload = {}
        self.payload.update(kw)

    def __exit__(self, *exc):
        if not _enabled or self.gen != _gen:
            # tracing was disabled or reset while this span was open —
            # e.g. a lingering daemon spoke thread the wheel spinner
            # deliberately survives, crossing a test fixture's
            # disable+reset(+re-enable).  Dropping the event keeps
            # foreign spans out of the next owner's ring.
            return False
        t1 = _perf()
        _buffer.add(Event(self.t0, threading.get_ident(),
                          self.track or thread_track(), self.name, "span",
                          t1 - self.t0, self.payload))
        return False


def span(track: str | None, name: str, **payload):
    """Context manager recording a duration event on ``track`` (None =
    the calling thread's track).  Disabled: returns the shared no-op
    singleton — nothing is allocated beyond the kwargs dict, so hot paths
    with payloads should guard on :func:`enabled` first."""
    if not _enabled:
        return _NULL
    return _Span(track, name, payload or None)


# ---------------------------------------------------------------------------
# Phases: one call site, three sinks (profiler trace, registry, ring).
# ---------------------------------------------------------------------------
#: Prefix of every annotation the program writes into a profiler trace:
#: ``tpusppy:<cylinder>:<name>``.
ANNOTATION_PREFIX = "tpusppy:"


_annotation_cls = None       # bound at first use: obs imports without jax


def _bind_annotation():
    global _annotation_cls
    try:
        from jax.profiler import TraceAnnotation as cls
    except ImportError:
        cls = contextlib.nullcontext     # takes, and ignores, the name
    _annotation_cls = cls
    return cls


def annotation(name: str):
    """``jax.profiler.TraceAnnotation("tpusppy:<cylinder>:<name>")`` for
    the calling thread: a flag check while no profiler session runs, a
    span on the thread's own line of the profiler's trace otherwise."""
    cls = _annotation_cls or _bind_annotation()
    return cls(f"{ANNOTATION_PREFIX}{cylinder()}:{name}")


_phase_sites: dict = {}      # (track, name) -> (annotation name, secs, count)


def _phase_site(track, name):
    # unlocked: two threads racing here write the same triple (the
    # registry hands both the one counter object)
    site = _phase_sites.get((track, name))
    if site is None:
        cyl = track.split(":", 1)[0]
        key = f"phase.{cyl}.{name}"
        site = _phase_sites[(track, name)] = (
            f"{ANNOTATION_PREFIX}{cyl}:{name}",
            _metrics.counter(key + ".secs"), _metrics.counter(key + ".count"))
    return site


class _Phase:
    __slots__ = ("name", "payload", "track", "site", "ann", "t0", "gen")

    def __init__(self, name, payload):
        self.name = name
        self.payload = payload

    def __enter__(self):
        self.track = track = getattr(_tls, "track", None) or "main"
        self.site = site = (_phase_sites.get((track, self.name))
                            or _phase_site(track, self.name))
        self.ann = (_annotation_cls or _bind_annotation())(site[0])
        self.gen = _gen
        self.ann.__enter__()
        self.t0 = _perf()
        return self

    def add(self, **kw):
        """Attach payload discovered mid-phase (ring only)."""
        if _enabled:
            if self.payload is None:
                self.payload = {}
            self.payload.update(kw)

    def __exit__(self, *exc):
        dur = _perf() - self.t0
        self.ann.__exit__(*exc)
        _, secs, count = self.site
        secs.inc(dur)
        count.inc(1)
        if _enabled and self.gen == _gen:
            _buffer.add(Event(self.t0, threading.get_ident(), self.track,
                              self.name, "span", dur, self.payload or None))
        return False


def phase(name: str, **payload):
    """Context manager for one coarse phase of the calling cylinder.
    Always: a profiler annotation ``tpusppy:<cylinder>:<name>`` and the
    registry counters ``phase.<cylinder>.<name>.{secs,count}``; with the
    ring enabled also a span event on the thread's track, ``payload`` and
    ``.add()`` as for :func:`span`.  Not for fine-grained sites: those
    keep :func:`span` and its free disabled path."""
    return _Phase(name, payload)


# ---------------------------------------------------------------------------
# Outcomes: what a solve spent and how it ended (registry only).
# ---------------------------------------------------------------------------
_outcome_sites: dict = {}    # (track, kind) -> {field: counter}


def outcome(kind: str, **fields):
    """Add each of ``fields`` to the registry counter
    ``solve.<cylinder>.<kind>.<field>``, the cylinder being the calling
    thread's as :func:`phase` resolves it.  Where a phase says how long,
    this says how much and how it ended (sweeps, budget, rows done,
    accepted); every field is a sum.  Always on, registry only: no ring
    event, no annotation.  A field passed as 0 still makes its counter,
    so a dump shows the zero."""
    track = getattr(_tls, "track", None) or "main"
    # unlocked, as _phase_site: racing threads are handed the one counter
    site = _outcome_sites.get((track, kind))
    if site is None:
        site = _outcome_sites[(track, kind)] = {}
    for field, n in fields.items():
        ctr = site.get(field)
        if ctr is None:
            cyl = track.split(":", 1)[0]
            ctr = site[field] = _metrics.counter(
                f"solve.{cyl}.{kind}.{field}")
        if n:
            ctr.inc(n)


def record_span(track: str | None, name: str, t0: float, dur: float,
                payload: dict | None = None):
    """Record an ALREADY-timed span (callers that measured their own
    ``perf_counter`` window, e.g. the host-sync fetch wrapper)."""
    if not _enabled:
        return
    _buffer.add(Event(t0, threading.get_ident(),
                      track or thread_track(), name, "span", dur, payload))


def instant(track: str | None, name: str, **payload):
    """Point event (a marker on the timeline)."""
    if not _enabled:
        return
    _buffer.add(Event(_perf(), threading.get_ident(),
                      track or thread_track(), name, "instant", None,
                      payload or None))


def counter(track: str | None, name: str, value, **payload):
    """Sampled numeric series (rendered as a counter track; the report
    collects named series like ``rel_gap`` into *-vs-wall arrays).
    Extra ``payload`` keys ride alongside ``value`` — the telemetry
    plane tags per-tenant samples with ``request_id``/``trace_id`` so
    the report can bucket series per request."""
    if not _enabled:
        return
    data = {"value": float(value)}
    if payload:
        data.update(payload)
    _buffer.add(Event(_perf(), threading.get_ident(),
                      track or thread_track(), name, "counter", None,
                      data))


# ---------------------------------------------------------------------------
# Flush / wiring
# ---------------------------------------------------------------------------
def flush(path: str | None = None) -> str | None:
    """Write the current ring as Perfetto JSON to ``path`` (default: the
    armed flush path) plus the report summary to ``<path>.report.json``.
    Returns the path written, or None when there is nowhere to write."""
    path = path or _flush_path
    if not path:
        return None
    import json

    from . import perfetto, report

    perfetto.export(events(), path=path)
    with open(path + ".report.json", "w") as f:
        json.dump(report.build_report(events()), f, indent=1)
    return path


def flush_if_enabled():
    """Flush when tracing is on and a path is armed (wheel/bench hook —
    safe to call unconditionally)."""
    if _enabled and _flush_path:
        flush()


def _flush_atexit():
    with contextlib.suppress(Exception):   # interpreter teardown
        flush_if_enabled()


def maybe_enable_from_config(cfg) -> bool:
    """Enable tracing when a Config carries a truthy ``tracing`` field
    (the path to flush to).  Returns whether tracing is now enabled."""
    path = None
    try:
        path = cfg.get("tracing")
    except Exception:
        path = getattr(cfg, "tracing", None)
    if path:
        enable(path=str(path))
    return _enabled


_env_path = os.environ.get("TPUSPPY_TRACE")
if _env_path:
    enable(path=_env_path)
