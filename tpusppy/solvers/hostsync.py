"""Host-sync accounting for dispatch-decision fetches.

Every device→host fetch on a dispatch decision path (segmented
continuations, the amortized solve loop, bench measurement windows) goes
through :func:`fetch` so the sync traffic is *observable*: trackers opened
with :func:`track` count the fetches and the host wall-time spent blocked
in them, and ``bench.py`` reports the totals per segment as
``host_sync_count`` / ``dispatch_overhead_pct`` next to ``mfu_pct``.

Why it matters: a host fetch blocks the dispatch that follows it, so a
fetch that gates the next dispatch leaves the device idle for the whole
round-trip.  The pipelined continuation
(:func:`tpusppy.solvers.segmented.continue_frozen`) marks fetches that
resolve while further device work is already queued as ``overlapped`` —
the host still blocks, but the device does not, so only NON-overlapped
fetch time counts as dispatch overhead.

:func:`fetch` is an EXPLICIT transfer (``jax.device_get``), which is the
transfer-guard contract: decision paths run clean under
``jax.transfer_guard_device_to_host("disallow")`` (which blocks only
implicit transfers such as ``np.asarray`` on a device array), so any
unplanned fetch added later fails loudly in the guard tests instead of
silently re-serializing the pipeline.
"""

from __future__ import annotations

import contextlib
import threading
import time

import numpy as np

from ..obs import metrics as _metrics
from ..obs import trace as _trace

_local = threading.local()

# process-wide absorption into the metrics registry (tpusppy.obs.metrics):
# every fetch feeds these counters so bench/report numbers come from ONE
# source; the thread-local trackers below remain the scoped per-window
# view (and the parity test pins that single-threaded windows agree)
_CTR_COUNT = _metrics.counter("host_sync.count")
_CTR_OVERLAPPED = _metrics.counter("host_sync.overlapped")
_CTR_BLOCKED = _metrics.counter("host_sync.blocked_secs")
_CTR_FETCH = _metrics.counter("host_sync.fetch_secs")
# the same two by cylinder (``host_sync.count.hub``, ...): the process-wide
# counters sum three cylinder threads, these say whose fetches blocked
_BY_CYLINDER: dict = {}


def _cylinder_counters(cyl):
    pair = _BY_CYLINDER.get(cyl)
    if pair is None:
        pair = _BY_CYLINDER[cyl] = (
            _metrics.counter("host_sync.count." + cyl),
            _metrics.counter("host_sync.blocked_secs." + cyl))
    return pair


def _stack():
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


def reset():
    """Drop the calling thread's tracker stack.

    Test-isolation hook (an autouse fixture calls it): a tracker left
    open by a failed/interrupted test — or pushed by library code that
    never unwound — must not keep counting fetches into a later test's
    ``host_sync_count`` assertion."""
    _local.stack = []


class SyncTracker:
    """Counts decision-path fetches and the host time spent blocked in
    them.  ``blocked_secs`` accumulates only NON-overlapped fetches (the
    ones that can leave the device idle); ``fetch_secs`` accumulates all.
    """

    def __init__(self):
        self.count = 0
        self.overlapped = 0
        self.blocked_secs = 0.0
        self.fetch_secs = 0.0

    def add(self, secs: float, overlapped: bool):
        self.count += 1
        self.fetch_secs += secs
        if overlapped:
            self.overlapped += 1
        else:
            self.blocked_secs += secs

    def overhead_pct(self, wall_secs: float) -> float:
        """Dispatch overhead: blocked-fetch time over a measured wall
        window (clipped to [0, 100] — clock skew must not produce >100)."""
        if wall_secs <= 0:
            return 0.0
        return float(min(100.0, 100.0 * self.blocked_secs / wall_secs))


@contextlib.contextmanager
def track():
    """Open a tracker for the current thread; nests (inner fetches land in
    every open tracker of this thread — cylinder threads never share)."""
    t = SyncTracker()
    _stack().append(t)
    try:
        yield t
    finally:
        _stack().remove(t)


def fetch(x, overlapped: bool = False):
    """Device→host fetch of an array or pytree, counted by the open
    trackers.  Explicit (``jax.device_get``) so decision paths satisfy the
    transfer-guard contract; numpy/scalar inputs pass through unchanged
    (scripted test stand-ins take this path)."""
    t0 = time.perf_counter()
    # on the profiler's clock too: the fetch as the calling cylinder's
    # ``tpusppy:<cylinder>:fetch`` beside the device's operations
    with _trace.annotation("fetch"):
        try:
            import jax
            out = jax.device_get(x)
        except ImportError:              # pure-host callers (unit tests)
            out = np.asarray(x)
    dt = time.perf_counter() - t0
    for tr in _stack():
        tr.add(dt, overlapped)
    count, blocked = _cylinder_counters(_trace.cylinder())
    _CTR_COUNT.inc(1)
    count.inc(1)
    _CTR_FETCH.inc(dt)
    if overlapped:
        _CTR_OVERLAPPED.inc(1)
    else:
        _CTR_BLOCKED.inc(dt)
        blocked.inc(dt)
    if _trace.enabled():
        # retroactive span: the fetch wall-time on the "host-sync" track
        _trace.record_span("host-sync", "fetch", t0, dt,
                           {"overlapped": overlapped})
    return out
