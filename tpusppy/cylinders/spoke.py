"""Spoke type lattice: typed bases for bound/W/nonant spokes.

TPU-native analogue of ``mpisppy/cylinders/spoke.py:18-376``.  A spoke runs an
opt object in its own cylinder (host thread here), puts its bound into its
hub-facing mailbox, polls the hub's outbound mailbox for W / nonant / bound
payloads with write-id freshness semantics, and exits on the kill sentinel
(write_id == -1, spoke.py:84-145).
"""

from __future__ import annotations

import enum
import math
import threading
import os
import time

import numpy as np

from ..obs import metrics as _metrics
from ..obs import trace as _trace
from ..resilience import faults as _faults
from ..resilience import supervisor as _supervisor
from .spcommunicator import KILL_ID, SPCommunicator


class ConvergerSpokeType(enum.Enum):
    OUTER_BOUND = 1
    INNER_BOUND = 2
    W_GETTER = 3
    NONANT_GETTER = 4


class Spoke(SPCommunicator):
    """Base spoke (spoke.py:24-145)."""

    def __init__(self, spbase_object, strata_rank, fabric, options=None):
        super().__init__(spbase_object, strata_rank, fabric, options)
        self.remote_write_id = 0
        self._recv_count = 0     # fresh hub payloads seen (fault-plan clock)
        # gauge hoisted out of the ~500 Hz poll loop (the registry
        # get-or-create costs a lock + dict probe per call)
        self._hb_gauge = _supervisor.heartbeat_gauge(
            f"spoke{self.strata_rank}")
        self._waiting = None     # the open ``wait`` phase, while spinning

    # lengths negotiated by WheelSpinner before mailbox construction
    def buffer_lengths(self) -> tuple[int, int]:
        """(spoke_to_hub_len, hub_to_spoke_len), excluding write-id slots."""
        raise NotImplementedError

    def spoke_to_hub(self, values):
        self.fabric.to_hub[self.strata_rank].put(values)

    def spoke_from_hub(self):
        """Snapshot the hub's outbound payload; True when fresh
        (spoke.py:84-118 with the all-ranks-agree vote collapsed: one host
        thread per cylinder reads one consistent snapshot)."""
        # liveness for the hub's supervisor: a spoke polling its mailbox
        # is alive even when it has nothing new to Put
        self._hb_gauge.set(time.monotonic())
        data, wid = self.fabric.to_spoke[self.strata_rank].get()
        self._locals = data
        if wid > self.remote_write_id or wid < 0:
            self.remote_write_id = wid
            if wid >= 0:
                self._recv_count += 1
                if _faults.active():   # deterministic dead-spoke injection
                    _faults.on_spoke_payload(self)
            return True
        return False

    def got_kill_signal(self) -> bool:
        self._new_locals = self.spoke_from_hub()
        if not self._new_locals:
            # nothing fresh: yield the core so the hub thread can progress
            # (the reference relies on MPI async progress for the same effect)
            if self._waiting is None:
                # ONE ``wait`` phase per stretch of spinning, not one per
                # 2 ms poll: it closes when the hub posts something new
                self._waiting = _trace.phase("wait").__enter__()
            time.sleep(0.002)
        elif self._waiting is not None:
            self._waiting.__exit__(None, None, None)
            self._waiting = None
        return self.remote_write_id == KILL_ID

    def bound_pass(self):
        """The ``pass`` phase of this spoke: one bound pass, from the hub's
        new W / nonants to the bound put."""
        return _trace.phase("pass")

    def peek_kill_signal(self) -> bool:
        """Kill check that does NOT consume payload freshness — safe to call
        mid-computation without causing the next ``got_kill_signal`` to treat
        a payload posted meanwhile as stale."""
        return self.fabric.to_spoke[self.strata_rank].write_id == KILL_ID

    def get_serial_number(self) -> int:
        return self.remote_write_id

    def main(self):
        raise NotImplementedError


class _BoundSpoke(Spoke):
    """A spoke that reports a single bound (spoke.py:147-208), with optional
    CSV bound tracing via options["trace_prefix"]."""

    def __init__(self, spbase_object, strata_rank, fabric, options=None):
        super().__init__(spbase_object, strata_rank, fabric, options)
        self._bound = 0.0
        self._locals = np.zeros(2)
        self._new_locals = False
        trace_prefix = spbase_object.options.get("trace_prefix")
        if trace_prefix is not None:
            filen = trace_prefix + self.__class__.__name__ + ".csv"
            if os.path.exists(filen):
                raise RuntimeError(f"Spoke trace file {filen} already exists!")
            with open(filen, "w") as f:
                f.write("time,bound\n")
            self.trace_filen = filen
            self.start_time = time.perf_counter()
        else:
            self.trace_filen = None

    def buffer_lengths(self):
        return 1, 2  # bound out; hub outer/inner bounds in

    @property
    def bound(self):
        return self._bound

    @bound.setter
    def bound(self, value):
        self._append_trace(value)
        self._bound = float(value)
        self.spoke_to_hub(np.array([self._bound]))

    @property
    def hub_outer_bound(self):
        return self._locals[-2]

    @property
    def hub_inner_bound(self):
        return self._locals[-1]

    def _append_trace(self, value):
        if self.trace_filen is None:
            return
        with open(self.trace_filen, "a") as f:
            f.write(f"{time.perf_counter() - self.start_time},{value}\n")


class InnerBoundSpoke(_BoundSpoke):
    """Inner bound, no hub data needed (spoke.py:239-244)."""
    converger_spoke_types = (ConvergerSpokeType.INNER_BOUND,)
    converger_spoke_char = 'I'


class OuterBoundSpoke(_BoundSpoke):
    """Outer bound, no hub data needed (spoke.py:246-252)."""
    converger_spoke_types = (ConvergerSpokeType.OUTER_BOUND,)
    converger_spoke_char = 'O'


class _BoundNonantLenSpoke(_BoundSpoke):
    """A bound spoke whose inbound payload is nonant-length (spoke.py:210-237):
    (S*K) values + hub outer/inner bounds."""

    def buffer_lengths(self):
        S = self.opt.batch.num_scenarios
        K = self.opt.nonant_length
        return 1, S * K + 2


class _BoundWSpoke(_BoundNonantLenSpoke):
    """Gets the hub's W (spoke.py:254-270)."""

    @property
    def localWs(self) -> np.ndarray:
        """(S, K) view of the hub's dual weights."""
        S = self.opt.batch.num_scenarios
        K = self.opt.nonant_length
        return self._locals[:-2].reshape(S, K)

    @property
    def new_Ws(self) -> bool:
        return self._new_locals


class OuterBoundWSpoke(_BoundWSpoke):
    """Outer bound from the hub's W, kept with its evidence: beside the best
    bound it reported, the per-scenario certificates ``d_s`` that sum to it
    and the ``W`` they were computed at, so that a reader can hold each
    ``d_s`` to the minimum of scenario ``s``'s own program at that ``W``
    after the wheel tore down (:meth:`best_certificates`), as
    :meth:`InnerBoundNonantSpoke.best_snapshot` lets one hold the inner
    bound to its point."""

    converger_spoke_types = (
        ConvergerSpokeType.OUTER_BOUND,
        ConvergerSpokeType.W_GETTER,
    )
    converger_spoke_char = 'O'

    def __init__(self, spbase_object, strata_rank, fabric, options=None):
        super().__init__(spbase_object, strata_rank, fabric, options)
        self.is_minimizing = self.opt.is_minimizing
        # (bound, certificates, W), written as one under the lock: the
        # reader may be another thread while this spoke is mid-pass
        self._best_lock = threading.Lock()
        self._best = (-math.inf if self.is_minimizing else math.inf,
                      None, None)

    def keep_if_best(self, bound, certificates, W) -> bool:
        """Keep ``certificates`` ((S,), whose expectation is ``bound``) and
        the ``W`` ((S, K)) behind them if ``bound`` is the best so far."""
        if bound is None or not np.isfinite(bound):
            return False
        with self._best_lock:
            best = self._best[0]
            if not (bound > best if self.is_minimizing else bound < best):
                return False
            self._best = (float(bound), np.array(certificates, dtype=float),
                          np.array(W, dtype=float))
        return True

    def best_certificates(self):
        """(bound, d, W): the best outer bound this spoke reported, its
        per-scenario certificates ``d`` (S,) (each with its scenario's
        objective constant in it) and the ``W`` (S, K) they were computed
        at; ``d`` and ``W`` are None until a finite bound came."""
        with self._best_lock:
            return self._best


class _BoundNonantSpoke(_BoundNonantLenSpoke):
    """Gets the hub's nonants (spoke.py:288-304)."""

    @property
    def localnonants(self) -> np.ndarray:
        """(S, K) view of the hub's current nonant values."""
        S = self.opt.batch.num_scenarios
        K = self.opt.nonant_length
        return self._locals[:-2].reshape(S, K)

    @property
    def new_nonants(self) -> bool:
        return self._new_locals


class InnerBoundNonantSpoke(_BoundNonantSpoke):
    """Incumbent finder over hub nonants, with best-solution cache
    (spoke.py:306-363)."""

    converger_spoke_types = (
        ConvergerSpokeType.INNER_BOUND,
        ConvergerSpokeType.NONANT_GETTER,
    )
    converger_spoke_char = 'I'

    def __init__(self, spbase_object, strata_rank, fabric, options=None):
        super().__init__(spbase_object, strata_rank, fabric, options)
        self.is_minimizing = self.opt.is_minimizing
        self.best_inner_bound = math.inf if self.is_minimizing else -math.inf
        self.best_solution_cache = None   # (S, n) full solutions
        # (bound, cache) are written as a pair; teardown may read them from
        # another thread while a hung spoke is still mid-update
        self._best_lock = threading.Lock()

    def update_if_improving(self, candidate_inner_bound) -> bool:
        if candidate_inner_bound is None or not np.isfinite(
                candidate_inner_bound):
            return False
        better = (candidate_inner_bound < self.best_inner_bound
                  if self.is_minimizing
                  else candidate_inner_bound > self.best_inner_bound)
        if not better:
            return False
        with self._best_lock:
            self.best_inner_bound = float(candidate_inner_bound)
            self.bound = self.best_inner_bound
            self._cache_best_solution()
        _metrics.inc("xhat.improved")
        return True

    def best_snapshot(self):
        """(bound, cache) read atomically w.r.t. update_if_improving —
        safe even while the spoke's main loop is still running."""
        with self._best_lock:
            return self.best_inner_bound, self.best_solution_cache

    def _cache_best_solution(self):
        if self.opt.local_x is not None:
            self.best_solution_cache = np.asarray(self.opt.local_x).copy()

    def finalize(self):
        if self.best_solution_cache is None:
            return None
        self.opt.local_x = self.best_solution_cache
        self.opt.first_stage_solution_available = True
        self.final_bound = self.bound
        return self.final_bound


class OuterBoundNonantSpoke(_BoundNonantSpoke):
    converger_spoke_types = (
        ConvergerSpokeType.OUTER_BOUND,
        ConvergerSpokeType.NONANT_GETTER,
    )
    converger_spoke_char = 'A'
