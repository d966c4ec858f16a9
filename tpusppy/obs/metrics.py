"""Process-wide metrics registry: counters, gauges, histograms.

The single source for every number the bench reports (the PR-3
``host_sync_count`` / ``dispatch_overhead_pct`` fragments grew into
this): :mod:`tpusppy.solvers.hostsync` feeds the ``host_sync.*``
counters on every decision-path fetch, the segmented dispatcher bills
``speculation.*``, the mailboxes count puts/skips, and so on — see
doc/observability.md for the key catalogue.

Metrics are ALWAYS on (unlike the trace ring): each update is one lock +
an int/float add, cheap enough for every hot path that already crosses
the host.  Scoped measurements (bench segments, tests) read via
:func:`window`, which snapshots the registry and exposes per-key deltas —
the process-wide totals never need resetting mid-run.  Values are
monotone within a process; :func:`reset` exists for test isolation only.

Concurrency note: the registry is process-global, so a window opened
while OTHER threads also update the same keys sees their traffic too
(the thread-local trackers in ``hostsync`` remain the per-cylinder
view; the parity test pins that single-threaded windows agree exactly).
"""

from __future__ import annotations

import threading


class Counter:
    """Monotone float/int accumulator."""

    __slots__ = ("_lock", "value")

    def __init__(self):
        self._lock = threading.Lock()
        self.value = 0.0

    def inc(self, n=1.0):
        with self._lock:
            self.value += n

    def get(self) -> float:
        with self._lock:
            return self.value

    def reset(self):
        with self._lock:
            self.value = 0.0


class Gauge:
    """Last-value-wins sample."""

    __slots__ = ("_lock", "value")

    def __init__(self):
        self._lock = threading.Lock()
        self.value = None

    def set(self, v):
        with self._lock:
            self.value = float(v)

    def get(self):
        with self._lock:
            return self.value

    def reset(self):
        with self._lock:
            self.value = None


class Histogram:
    """Streaming summary (count/total/min/max) plus QUANTILES from a
    bounded reservoir — serving SLOs need latency percentiles, not just
    sums (doc/serving.md).  The reservoir is classic Algorithm-R
    sampling (uniform over the stream) capped at :data:`RESERVOIR_CAP`
    samples, seeded deterministically so identical insert streams yield
    identical summaries."""

    RESERVOIR_CAP = 512

    __slots__ = ("_lock", "count", "total", "min", "max", "_samples",
                 "_rng")

    def __init__(self):
        import random

        self._lock = threading.Lock()
        self.count = 0
        self.total = 0.0
        self.min = None
        self.max = None
        self._samples: list = []
        self._rng = random.Random(0x5EED)

    def add(self, v):
        v = float(v)
        with self._lock:
            self.count += 1
            self.total += v
            self.min = v if self.min is None else min(self.min, v)
            self.max = v if self.max is None else max(self.max, v)
            if len(self._samples) < self.RESERVOIR_CAP:
                self._samples.append(v)
            else:
                j = self._rng.randrange(self.count)
                if j < self.RESERVOIR_CAP:
                    self._samples[j] = v

    def quantile(self, q: float):
        """Reservoir quantile (nearest-rank on sorted samples); None when
        empty.  Exact while count <= RESERVOIR_CAP, sampled past it."""
        with self._lock:
            samples = sorted(self._samples)
        if not samples:
            return None
        q = min(max(float(q), 0.0), 1.0)
        idx = min(len(samples) - 1, int(round(q * (len(samples) - 1))))
        return samples[idx]

    def summary(self) -> dict:
        p50, p95, p99 = (self.quantile(q) for q in (0.50, 0.95, 0.99))
        with self._lock:
            return {"count": self.count, "total": self.total,
                    "min": self.min, "max": self.max,
                    "p50": p50, "p95": p95, "p99": p99}

    def reset(self):
        with self._lock:
            self.count = 0
            self.total = 0.0
            self.min = None
            self.max = None
            self._samples = []
            import random

            self._rng = random.Random(0x5EED)


class Registry:
    """Name -> metric store with get-or-create accessors."""

    def __init__(self):
        self._lock = threading.Lock()
        self._metrics: dict = {}

    def _get(self, name: str, cls):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = cls()
            elif not isinstance(m, cls):
                raise TypeError(
                    f"metric {name!r} is {type(m).__name__}, "
                    f"wanted {cls.__name__}")
            return m

    def counter(self, name: str) -> Counter:
        return self._get(name, Counter)

    def gauge(self, name: str) -> Gauge:
        return self._get(name, Gauge)

    def histogram(self, name: str) -> Histogram:
        return self._get(name, Histogram)

    def value(self, name: str, default=0.0):
        """Current scalar value of a counter/gauge (0.0 for unknown keys —
        a window over an idle subsystem reads as zero traffic)."""
        with self._lock:
            m = self._metrics.get(name)
        if m is None:
            return default
        if isinstance(m, Histogram):
            return m.summary()["total"]
        return m.get()

    def dump(self) -> dict:
        """{name: value-or-summary} snapshot of everything."""
        with self._lock:
            items = list(self._metrics.items())
        out = {}
        for name, m in sorted(items):
            out[name] = (m.summary() if isinstance(m, Histogram)
                         else m.get())
        return out

    def reset(self):
        """Zero every metric IN PLACE (test isolation; never call
        mid-run).  In place matters: instrumented modules bind their hot
        counters at import time (``hostsync._CTR_COUNT`` etc.) — dropping
        the objects would orphan those references and silently fork the
        registry."""
        with self._lock:
            for m in self._metrics.values():
                m.reset()


#: The process-wide registry every subsystem feeds.
REGISTRY = Registry()


# Module-level conveniences (the common call shape in instrumentation).
def counter(name: str) -> Counter:
    return REGISTRY.counter(name)


def gauge(name: str) -> Gauge:
    return REGISTRY.gauge(name)


def histogram(name: str) -> Histogram:
    return REGISTRY.histogram(name)


def inc(name: str, n=1.0):
    REGISTRY.counter(name).inc(n)


def value(name: str, default=0.0):
    return REGISTRY.value(name, default)


def dump() -> dict:
    return REGISTRY.dump()


def reset():
    REGISTRY.reset()


class Window:
    """Delta view over the registry: snapshots counter/histogram totals
    at entry; ``delta(name)`` is the traffic since then.  Gauges read
    current (their delta is rarely meaningful)."""

    def __init__(self, registry: Registry | None = None):
        self.registry = registry or REGISTRY
        self._base: dict = {}

    def __enter__(self):
        # histograms snapshot as their running TOTAL (value() semantics)
        # so delta() is a real window delta for them too, not the
        # lifetime figure
        self._base = {
            k: (v["total"] if isinstance(v, dict) else v)
            for k, v in self.registry.dump().items()
        }
        return self

    def __exit__(self, *exc):
        return False

    def delta(self, name: str) -> float:
        base = self._base.get(name, 0.0)
        cur = self.registry.value(name, 0.0)
        if cur is None or isinstance(cur, dict):
            return 0.0
        return cur - (base or 0.0)

    def deltas(self) -> dict:
        """{name: windowed value} for every metric: counters and
        histograms as deltas since entry, gauges at their current value
        (a gauge delta is rarely meaningful).  The per-segment report
        uses this so one bench segment's counter dump never carries the
        previous segments' traffic."""
        with self.registry._lock:
            items = list(self.registry._metrics.items())
        out = {}
        for k, m in sorted(items):
            if isinstance(m, Gauge):
                out[k] = m.get()
            elif isinstance(m, Histogram):
                out[k] = m.summary()["total"] - (self._base.get(k) or 0.0)
            else:
                out[k] = m.get() - (self._base.get(k) or 0.0)
        return out


def window(registry: Registry | None = None) -> Window:
    """Context manager for scoped measurement (bench segments, tests)."""
    return Window(registry)
