"""Reduction of a ``jax.profiler`` trace (``*.xplane.pb``) to the device's
busy and idle time, the programs that held it longest, and its idle time
by what the host was doing.

Read with nothing but jax (``jax.profiler.ProfileData``).  The slice lies
between two marker annotations that the harness writes when it starts and
stops the trace; the gaps are attributed to the harness's other ``bench:*``
annotations (``bench:hub_step``, ``bench:hub_linger``, ``bench:request``),
the innermost that covers the gap's middle and that ended inside the slice.
The hub's boundaries are in the trace as well, as instants named
``bench:iter=<iteration>``: between the first and the last of a slice the
hub iterations are counted and each compiled program's time on the device
is summed, both on the trace's own clock.

Pure functions over (name, start_ns, end_ns) tuples do the arithmetic, so
the tests check them on made-up intervals and on a small recorded trace.
"""

from __future__ import annotations

START, STOP = "bench:trace_start", "bench:trace_stop"
PREFIX = "bench:"
ITER = "bench:iter="           # an instant at a hub boundary: ITER + iteration
# the lines of a v5e device plane that are read: one event per executed
# operation, and one per run of a compiled program.  Each stands in for the
# other where a plane lacks one ("Steps", "Async XLA Ops" repeat them)
OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)


def short(name):
    """An operation's name as the trace prints it is its whole HLO line:
    keep what stands before `` = `` (``%fused_sweeps.5``)."""
    return name.split(" = ", 1)[0][:80]


def load_file(path):
    import jax

    return load(jax.profiler.ProfileData.from_file(path))


def load(data):
    """{plane name: {line name: [(event name, start_ns, end_ns), ...]}}.
    Lines of one name in one plane are merged.  Only what the reduction
    reads is kept (a device plane's operation and module lines, the host's
    ``bench:`` annotations): a traced slice holds millions of events."""
    planes = {}
    for plane in data.planes:
        device = is_device_plane(plane.name)
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            if device and line.name not in OP_LINES + MODULE_LINES:
                continue
            evs = lines.setdefault(line.name, [])
            for ev in line.events:
                name = ev.name
                if not device and not name.startswith(PREFIX):
                    continue
                start = float(ev.start_ns)
                evs.append((short(name), start,
                            start + float(ev.duration_ns)))
    return planes


def is_device_plane(name):
    return name.startswith("/device:TPU:") and "SparseCore" not in name


def op_events(lines, prefer=OP_LINES, other=MODULE_LINES):
    """The events of a device plane that are operations running."""
    for group in (prefer, other):
        evs = [e for name in group for e in lines.get(name, ())]
        if evs:
            return evs
    return []


def program_events(lines):
    """One event per run of a compiled program, named for the program
    (``jit_mega``; the trace appends the program's fingerprint)."""
    return [(name.split("(", 1)[0], s, e)
            for name, s, e in op_events(lines, MODULE_LINES, OP_LINES)]


def union(intervals):
    """Sorted, disjoint (start, end) covering the same instants."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(merged, t0, t1):
    return [(max(s, t0), min(e, t1)) for s, e in merged
            if min(e, t1) > max(s, t0)]


def busy_ns(merged, t0, t1):
    return sum(e - s for s, e in clip(merged, t0, t1))


def gaps(merged, t0, t1):
    """The idle stretches of [t0, t1], longest first."""
    out, at = [], t0
    for s, e in clip(merged, t0, t1):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if t1 > at:
        out.append((at, t1))
    return sorted(out, key=lambda g: g[0] - g[1])


def attribute(gap_list, annotations):
    """{annotation name: idle ns}: each gap goes to the shortest annotation
    that covers its middle, or to ``"unannotated"``."""
    out = {}
    for s, e in gap_list:
        mid = 0.5 * (s + e)
        cover = [(a1 - a0, name) for name, a0, a1 in annotations
                 if a0 <= mid <= a1]
        name = min(cover)[1] if cover else "unannotated"
        out[name] = out.get(name, 0.0) + (e - s)
    return out


def iteration_span(marks, t0, t1):
    """(start_ns, end_ns, iterations) between the first and the last hub
    boundary inside [t0, t1], from ``(t_ns, iteration)`` marks; None where
    the slice holds no whole iteration.  A count that falls (the next
    request's hub starts at 0) adds nothing."""
    inside = sorted(m for m in marks if t0 <= m[0] <= t1)
    n = sum(max(0, b[1] - a[1]) for a, b in zip(inside, inside[1:]))
    if n <= 0:
        return None
    return inside[0][0], inside[-1][0], n


def program_busy(progs, t0, t1):
    """{program: ns in which one of its runs was on the device inside
    [t0, t1]}, from (name, start, end) events of one device."""
    by = {}
    for name, s, e in progs:
        by.setdefault(name, []).append((s, e))
    out = {name: busy_ns(union(iv), t0, t1) for name, iv in by.items()}
    return {name: ns for name, ns in out.items() if ns > 0}


def top(pairs, k=10):
    tot = {}
    for name, ns in pairs:
        tot[name] = tot.get(name, 0.0) + ns
    return [[n, v / 1e9] for n, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def reduce_planes(planes):
    """The numbers of one trace.  ``busy_s`` is the mean over the device
    planes of the time in which an operation ran inside the window."""
    notes = [(name, s, e)
             for pname, lines in planes.items() if not is_device_plane(pname)
             for evs in lines.values()
             for name, s, e in evs if name.startswith(PREFIX)]
    dev = {p: op_events(l) for p, l in planes.items() if is_device_plane(p)}
    dev = {p: evs for p, evs in dev.items() if evs}
    progs = {p: program_events(planes[p]) for p in dev}
    if not dev:
        raise RuntimeError(
            "the trace holds no operation on a TPU plane "
            f"(planes: {sorted(planes)})")
    marks = {name: (s, e) for name, s, e in notes if name in (START, STOP)}
    if len(marks) == 2:
        t0, t1 = marks[START][1], marks[STOP][0]
    else:
        t0 = min(s for evs in dev.values() for _, s, _ in evs)
        t1 = max(e for evs in dev.values() for _, _, e in evs)
    inner = [n for n in notes if n[0] not in (START, STOP)
             and not n[0].startswith(ITER)]
    span = iteration_span([(s, int(name[len(ITER):])) for name, s, _ in notes
                           if name.startswith(ITER)], t0, t1)
    busy, gap_ns, ops, per_prog = [], {}, [], {}
    for plane, evs in dev.items():
        merged = union((s, e) for _, s, e in evs)
        busy.append(busy_ns(merged, t0, t1))
        for name, ns in attribute(gaps(merged, t0, t1), inner).items():
            gap_ns[name] = gap_ns.get(name, 0.0) + ns / len(dev)
        ops += [(name, (min(e, t1) - max(s, t0)) / len(dev))
                for name, s, e in progs[plane] if min(e, t1) > max(s, t0)]
        if span is not None:
            for name, ns in program_busy(progs[plane], *span[:2]).items():
                per_prog[name] = per_prog.get(name, 0.0) + ns / len(dev) / 1e9
    busy_s = sum(busy) / len(busy) / 1e9
    window_s = (t1 - t0) / 1e9
    return {
        "busy_s": busy_s, "window_s": window_s,
        "idle_pct": 100.0 * (1.0 - busy_s / window_s),
        "devices": len(dev), "window_from_markers": len(marks) == 2,
        "device_ops": top(ops),
        "idle_gaps": top(gap_ns.items()),
        # between the slice's first and last hub boundary: how many hub
        # iterations, how long, and each program's seconds on the device
        "iterations": None if span is None else {
            "count": span[2], "span_s": (span[1] - span[0]) / 1e9,
            "program_busy_s": per_prog},
    }


class Tracer:
    """One traced slice: from :meth:`start` to the first of :meth:`stop`
    and ``max_seconds``.  The trace holds the device's operations and the
    host's TraceMe annotations, not the Python call stack (a second of
    which is a hundred megabytes); two marker annotations bound the slice
    on the trace's own clock, whichever thread ends it.  :meth:`stop` only
    ends the session; the trace is read in :meth:`result`, after the
    window."""

    def __init__(self, max_seconds, keep_path=None):
        import threading

        self.max_seconds = float(max_seconds)
        self.keep_path = keep_path      # where to write the XSpace, if kept
        self._xspace = None
        self._lock = threading.Lock()
        self._timer = None
        self.running = False

    def start(self):
        import tempfile
        import threading

        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        # the session wants a directory; nothing is exported into it
        jax.profiler.start_trace(tempfile.gettempdir(), profiler_options=opts)
        self.running = True
        with jax.profiler.TraceAnnotation(START):
            pass
        self._timer = threading.Timer(self.max_seconds, self.stop)
        self._timer.daemon = True
        self._timer.start()

    def stop(self):
        """End the slice (once; a later call waits for the first)."""
        import jax
        from jax._src import profiler as _p

        with self._lock:
            if not self.running:
                return
            self.running = False
            with jax.profiler.TraceAnnotation(STOP):
                pass
            # jax.profiler.stop_trace() also writes a trace.json.gz, which
            # for a slice of millions of operations takes minutes: take the
            # session's XSpace as stop_and_get_fdo_profile does
            with _p._profile_state.lock:
                self._xspace = _p._profile_state.profile_session.stop()
                _p._profile_state.reset()
            if self.keep_path:
                with open(self.keep_path, "wb") as f:
                    f.write(self._xspace)

    def result(self):
        import jax

        self.stop()
        if self._timer is not None:
            self._timer.cancel()
        return reduce_planes(load(
            jax.profiler.ProfileData.from_serialized_xspace(self._xspace)))
