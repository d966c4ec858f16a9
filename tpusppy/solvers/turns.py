"""Device turns: the order in which a wheel's cylinders take one chip.

The cylinders of a wheel are host threads that share one device, and the
device runs programs in the order they were enqueued, each to its end.
While programs are short that queue needs no care.  Once they are long
(sslp at S=2000: a refresh solve 1.8 s, a megastep window 5.2 s, a dive's
cold solve 2.1 s) the queue decides who makes progress: the hub's step
waits behind whatever spoke program was enqueued a moment earlier, so the
seconds a hub iteration takes follow the spokes' own rhythm (how many
rounds this seed's dives need), lock into one of several repeating
patterns, and differ by several percent between runs of the same code.

:class:`DeviceTurns` replaces the queue's accident with a rule.  A wheel
makes one; the hub brackets each of its device steps (a solve with its
fetch, a megastep window with its fetch) in :func:`hub_step`, and the
shared-A engine brackets each piece of a spoke's solve in :func:`chunk`.
It stays out of the way until a hub step that compiled nothing has held
the device for ``engage_secs`` (short programs interleave well enough, and
the families whose programs are short pay two clock reads a step); a step
or a piece during which the process compiled is never billed.  From then on one
program holds the device at a time, and who goes next is decided by time
already used: the spokes together are owed ``spoke_share`` of the device
seconds the gate has handed out.  A spoke piece runs only while the spokes
are owed; the hub runs when they are not, or when none of them is waiting.
Both sides give way rather than let the device stand idle: a hub that is
owed nothing but is busy on the host longer than ``PATIENCE_SECS`` lets a
waiting spoke through, and a hub that owes waits at most ``GRACE_SHARE`` of
its last step for a spoke to come back from its own host work.

Because the device cannot be taken back from a running program, the
spokes' share is kept only to within one piece.  The shared-A engine
therefore hands an engaged gate its adaptive solves restart by restart
(:func:`tpusppy.solvers.shared_admm.adaptive_in_turns`), a quarter of
a solve at a time.

Waiting for a turn is the phase ``turn`` of the waiting cylinder
(``obs/trace.py``).  Counters (``tpusppy.obs.metrics``): ``turns.engaged``
(wheels whose gate engaged), ``turns.hub_secs`` / ``turns.spoke_secs``
(device seconds handed out while engaged), ``turns.hub_wait_secs`` /
``turns.spoke_wait_secs`` (seconds spent waiting for a turn).
"""

from __future__ import annotations

import collections
import contextlib
import threading
import time

from ..obs import metrics as _metrics
from ..obs import trace as _trace

#: a hub step that holds the device this long makes the queue worth ordering
ENGAGE_SECS = 1.0
#: the spokes' share of the device seconds an engaged gate hands out
SPOKE_SHARE = 1.0 / 3.0
#: a hub that owes waits this share of its last step for a spoke to show up
GRACE_SHARE = 0.05
#: a spoke that is owed nothing goes anyway once the hub has stayed away
#: from the device this long (the hub is busy on the host: rescue, linger)
PATIENCE_SECS = 0.05
#: nobody waits for a turn longer than this, whatever the accounts say
MAX_WAIT_SECS = 120.0

_tls = threading.local()
_compiles = 0        # backend compiles this process has seen (any thread)
_listening = False


def _listen():
    """Count jax's backend compiles (persistent-cache loads among them):
    a step that compiled its program says nothing of how long the program
    holds the device."""
    global _listening
    if _listening:
        return
    _listening = True
    import jax.monitoring

    def on_duration(event, _secs, **_kw):
        global _compiles
        if event.endswith("backend_compile_duration"):
            _compiles += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)


def _clean(compiles_before) -> bool:
    """Whether nothing compiled since ``compiles_before`` was read."""
    return _compiles == compiles_before


class DeviceTurns:
    """One wheel's gate (see the module docstring)."""

    def __init__(self, spoke_share: float | None = None,
                 engage_secs: float | None = None):
        share = SPOKE_SHARE if spoke_share is None else spoke_share
        _listen()
        self._cv = threading.Condition()
        self._owed_per_hub_sec = share / (1.0 - share)
        self.engage_secs = float(
            ENGAGE_SECS if engage_secs is None else engage_secs)
        self.engaged = False
        self.closed = False
        self._holder = None          # "hub" | "spoke" | None
        self._queue = collections.deque()   # waiting spokes, oldest first
        self._hub_waiting = False
        self._hub_secs = 0.0
        self._spoke_secs = 0.0
        self._hub_last = 0.0         # seconds of the hub's last step
        self._hub_longest = 0.0      # and of its longest
        self._hub_left = time.monotonic()   # when the hub last let go

    # -- accounts ---------------------------------------------------------
    def _owed(self) -> bool:
        return self._spoke_secs < self._owed_per_hub_sec * self._hub_secs

    def accounts(self):
        """(hub seconds, spoke seconds) handed out since engagement."""
        with self._cv:
            return self._hub_secs, self._spoke_secs

    # -- the hub ----------------------------------------------------------
    def _hub_acquire(self):
        if not self.engaged or self.closed:
            return
        with self._cv, _trace.phase("turn"):
            t0 = time.monotonic()
            # (spokes that never took a turn are not waited for: a dense
            # family whose gate engaged pays nothing)
            grace = t0 + (GRACE_SHARE * self._hub_last
                          if self._spoke_secs > 0.0 else 0.0)
            self._hub_waiting = True
            try:
                while not self.closed:
                    now = time.monotonic()
                    if now - t0 > MAX_WAIT_SECS:
                        break
                    if self._holder is None:
                        if not self._owed():
                            break
                        if not self._queue and now >= grace:
                            break
                    self._cv.wait(0.005 if not self._queue else 0.25)
            finally:
                self._hub_waiting = False
            self._holder = "hub"
            _metrics.inc("turns.hub_wait_secs", time.monotonic() - t0)

    def _hub_release(self, secs, clean=True):
        with self._cv:
            self._hub_left = time.monotonic()
            if self.engaged and not self.closed:
                self._holder = None
            if not clean or self.closed:
                pass                 # a step that compiled is not billed
            elif self.engaged:
                self._hub_last = secs
                self._hub_secs += secs
                self._hub_longest = max(self._hub_longest, secs)
                # spokes that stay away (host work, nothing to solve) are
                # not owed without end: two of the hub's longest steps
                self._spoke_secs = max(
                    self._spoke_secs, self._owed_per_hub_sec
                    * (self._hub_secs - 2.0 * self._hub_longest))
                _metrics.inc("turns.hub_secs", secs)
            elif secs >= self.engage_secs:
                # this step's seconds include what it queued behind; the
                # accounts open at zero with the next program
                self.engaged = True
                _metrics.inc("turns.engaged")
            self._cv.notify_all()

    # -- the spokes -------------------------------------------------------
    def _spoke_acquire(self):
        if not self.engaged or self.closed:
            return False
        with self._cv, _trace.phase("turn"):
            t0 = time.monotonic()
            me = object()
            self._queue.append(me)
            try:
                while not self.closed:
                    now = time.monotonic()
                    if now - t0 > MAX_WAIT_SECS:
                        break
                    if self._holder is None and self._queue[0] is me:
                        if self._owed():
                            break
                        if (not self._hub_waiting
                                and now - self._hub_left > PATIENCE_SECS):
                            break
                    self._cv.wait(0.01)
            finally:
                self._queue.remove(me)
            if self.closed:
                return False
            self._holder = "spoke"
            _metrics.inc("turns.spoke_wait_secs", time.monotonic() - t0)
            return True

    def _spoke_release(self, secs, clean=True):
        with self._cv:
            self._holder = None
            if clean:
                # nor do the spokes run up a lead without end
                self._spoke_secs = min(
                    self._spoke_secs + secs, self._owed_per_hub_sec
                    * (self._hub_secs + 2.0 * self._hub_longest))
                _metrics.inc("turns.spoke_secs", secs)
            self._cv.notify_all()

    def close(self):
        """Let everybody through from now on (the hub left its loop)."""
        with self._cv:
            self.closed = True
            self._holder = None
            self._cv.notify_all()


def in_order_device() -> bool:
    """Whether a gate belongs on the default device: an accelerator runs
    one program at a time, in the order they were enqueued, where the CPU
    backend runs the programs of several threads side by side (a gate
    would only idle its cores); and one process drives it (a gate decides
    by its own clock, so the processes of one mesh would order their
    collectives differently)."""
    import jax

    return jax.devices()[0].platform != "cpu" and jax.process_count() == 1


def join(gate: DeviceTurns | None, role: str | None):
    """Make the calling cylinder thread a party of ``gate`` as ``role``
    (``"hub"`` or ``"spoke"``); ``join(None, None)`` leaves."""
    _tls.gate = gate
    _tls.role = role
    _tls.depth = 0


def pieces() -> bool:
    """Whether the caller should hand its solve over piece by piece: a
    spoke of an engaged gate, outside any turn it already holds."""
    gate = getattr(_tls, "gate", None)
    return (gate is not None and gate.engaged and not gate.closed
            and _tls.role == "spoke" and _tls.depth == 0)


@contextlib.contextmanager
def hub_step():
    """One device step of the hub, dispatch to fetch.  Anybody else's
    call passes through."""
    gate = getattr(_tls, "gate", None)
    if gate is None or _tls.role != "hub" or _tls.depth:
        yield
        return
    gate._hub_acquire()
    _tls.depth = 1
    t0, c0 = time.monotonic(), _compiles
    try:
        yield
    finally:
        _tls.depth = 0
        gate._hub_release(time.monotonic() - t0, _clean(c0))


@contextlib.contextmanager
def chunk():
    """One piece of a spoke's device work.  The body must end with the
    piece done on the device (``jax.block_until_ready``) when it yields
    True: the seconds it held are billed to the spokes."""
    gate = getattr(_tls, "gate", None)
    if gate is None or _tls.role != "spoke" or _tls.depth:
        yield False
        return
    held = gate._spoke_acquire()
    if not held:
        yield False
        return
    _tls.depth = 1
    t0, c0 = time.monotonic(), _compiles
    try:
        yield True
    finally:
        _tls.depth = 0
        gate._spoke_release(time.monotonic() - t0, _clean(c0))
