"""The program's own phases beside the device's runs, from one profiler
trace: which cylinder launched each run of a compiled program, and which
idle stretch of the device no phase of the program covers.

The program (``tpusppy/obs/trace.py``) writes every coarse phase of a
cylinder into the profiler's trace as ``tpusppy:<cylinder>:<name>``, on the
line of the thread that runs it.  A run on the device is tied to the thread
that launched it by a chain of events of one trace, each link an id that one
event produces (stats ``_pt``, ``_p``) and another consumes (``_ct``, ``_c``)
(``tests/data/threads.xplane.pb`` shows both shapes of it):

  thread's line    ``PJRT_LoadedExecutable_Execute linkage``     produces A
  runtime's line   ``PJRT_LoadedExecutable_Execute``             consumes A
    inside it        ``tpu::System::Execute``                    produces B
  that line, or a  ``tpu::System::Execute=>IssueSequencedEvent`` consumes B
  worker's (a launch that had to wait for its inputs is enqueued by a
  worker thread of the runtime)
    inside it        ``DoEnqueueProgram``                        ``run_id``
  device plane     ``XLA Modules`` event                         ``run_id``

The chain is walked upward from the ``DoEnqueueProgram``: the events that
enclose it on its line, the producers of what they consume, the events that
enclose those, until a producer stands on a line that carries ``tpusppy:``
phases: the launching thread.
A run whose chain has a link missing is counted as unjoined, never guessed.
Only the host's lines and the device's ``XLA Modules`` line are read (some
thousands of events; ``XLA Ops`` holds millions), and lines are kept apart:
every Python thread's line has the same name.

:func:`of` gives the reduction for one traced run, computed once per ``obs``;
pure functions over tuples do the arithmetic and reuse ``tracered``'s
interval functions.  With a program that writes no ``tpusppy:`` annotation
(the parent of the PR that added them) every reader finds nothing and
returns ``None``.
"""

from __future__ import annotations

import bisect
import fnmatch
import json
import sys

from . import tracered

PREFIX = "tpusppy:"
ENQUEUE = "DoEnqueueProgram"
MAX_HOPS = 8                  # producer-to-consumer links in one chain
# in place of a cylinder: a run that was launched before the trace began (its
# run_id is lower than every enqueue's in the trace) and ran inside it.  The
# device's queue holds a few programs, so a slice begins with some
BEFORE = "before_slice"
_KEY = "_progtrace"


def load(data):
    """What the reduction reads of a ``ProfileData``:

    ``lines``     one dict per host line: ``phases`` [(cylinder, name, t0,
                  t1)], ``produces`` [(t, id)] and ``consumes`` [(id, t0,
                  t1)] (an id is its type and number), ``enqueues`` [(t,
                  run_id)], ``marks`` [(name, t0, t1)] (the harness's
                  ``bench:`` annotations)
    ``runs``      {device plane: [(program, t0, t1, run_id)]}
    """
    lines, runs = [], {}
    for plane in data.planes:
        if tracered.is_device_plane(plane.name):
            for line in plane.lines:
                if line.name in tracered.MODULE_LINES:
                    evs = runs.setdefault(plane.name, [])
                    for ev in line.events:
                        t0 = float(ev.start_ns)
                        evs.append((ev.name.split("(", 1)[0], t0,
                                    t0 + float(ev.duration_ns),
                                    dict(ev.stats).get("run_id")))
            continue
        for line in plane.lines:
            rec = {"phases": [], "produces": [], "consumes": [],
                   "enqueues": [], "marks": []}
            for ev in line.events:
                name = ev.name
                t0 = float(ev.start_ns)
                t1 = t0 + float(ev.duration_ns)
                if name.startswith(PREFIX):
                    cyl, _, phase = name[len(PREFIX):].partition(":")
                    rec["phases"].append((cyl, phase, t0, t1))
                    continue
                if name.startswith(tracered.PREFIX):
                    rec["marks"].append((name, t0, t1))
                    continue
                stats = dict(ev.stats)
                if "_p" in stats:
                    rec["produces"].append(
                        (t0, (stats.get("_pt"), stats["_p"])))
                if "_c" in stats:
                    rec["consumes"].append(
                        ((stats.get("_ct"), stats["_c"]), t0, t1))
                if name == ENQUEUE:
                    rec["enqueues"].append((t0, stats.get("run_id")))
            if any(rec.values()):
                lines.append(rec)
    return {"lines": lines, "runs": runs}


# -- pure functions over tuples -----------------------------------------------

def nest(spans):
    """Spans of one line [(a, b, t0, t1)] (a thread's phases: cylinder and
    name) sorted by start, with each one's parent (the span it lies in: a
    thread's spans nest) as a last field, -1 at the top: what
    :func:`innermost` looks up in."""
    order = sorted(spans, key=lambda p: (p[2], -p[3]))
    out, stack = [], []
    for k, (a, b, t0, t1) in enumerate(order):
        while stack and order[stack[-1]][3] < t0:
            stack.pop()
        out.append((a, b, t0, t1, stack[-1] if stack else -1))
        stack.append(k)
    return out


def innermost(nested, t):
    """Index in ``nested`` of the innermost phase that covers ``t``, -1
    where none does."""
    k = bisect.bisect_right(nested, t, key=lambda p: p[2]) - 1
    while k >= 0 and t > nested[k][3]:
        k = nested[k][4]
    return k


def cylinder_at(nested, t):
    """(cylinder, phase) of a thread at ``t``, from its line's phases as
    :func:`nest` gives them: the innermost phase that covers ``t``; else,
    with a phase of ``None``, the cylinder of the phase that started last
    before ``t`` (a thread changes cylinder only between phases), or of the
    first one after; ``(None, None)`` on a line with no phase."""
    if not nested:
        return None, None
    k = innermost(nested, t)
    if k >= 0:
        return nested[k][0], nested[k][1]
    k = bisect.bisect_right(nested, t, key=lambda p: p[2]) - 1
    return nested[max(k, 0)][0], None


def join(lines, runs):
    """[(program, t0, t1, cylinder, phase)] for the device runs ``runs``
    [(program, t0, t1, run_id)]; cylinder ``None`` where a link of the chain
    is missing or names two things, :data:`BEFORE` where the run was
    enqueued before the trace began."""
    producer = {}                  # id -> (line index, t), None if twice
    enqueue = {}                   # run_id -> (line index, t), None if twice
    for i, rec in enumerate(lines):
        for t, ident in rec["produces"]:
            producer[ident] = None if ident in producer else (i, t)
        for t, run_id in rec["enqueues"]:
            enqueue[run_id] = None if run_id in enqueue else (i, t)
    phases = [nest(rec["phases"]) for rec in lines]
    consumed = [nest([(ident, None, t0, t1)
                      for ident, t0, t1 in rec["consumes"]]) for rec in lines]

    def launcher(i, t, hops):
        """(line, t) of the launch on a thread's own line that led to what
        happens at ``t`` on line ``i``."""
        k = innermost(consumed[i], t)
        while k >= 0 and hops > 0:
            src = producer.get(consumed[i][k][0])
            if src is not None:
                found = src if phases[src[0]] else launcher(*src, hops - 1)
                if found is not None:
                    return found
            k = consumed[i][k][4]
        return None

    first = min((r for r in enqueue if r is not None), default=None)
    out = []
    for program, t0, t1, run_id in runs:
        cyl = phase = None
        at = enqueue.get(run_id) if run_id is not None else None
        src = launcher(*at, MAX_HOPS) if at is not None else None
        if src is not None:
            cyl, phase = cylinder_at(phases[src[0]], src[1])
        elif (run_id is not None and run_id not in enqueue
              and first is not None and run_id < first):
            cyl = BEFORE
        out.append((program, t0, t1, cyl, phase))
    return out


def device_time(joined, t0, t1):
    """{cylinder: {phase: ns}} of the joined runs inside [t0, t1]; the
    unjoined under cylinder ``None``.  One device runs one program at a
    time, so a plain sum of clipped runs is the device's time."""
    out = {}
    for _program, s, e, cyl, phase in joined:
        ns = min(e, t1) - max(s, t0)
        if ns > 0:
            by = out.setdefault(cyl, {})
            by[phase] = by.get(phase, 0.0) + ns
    return out


def idle_by_phase(gap_list, nested_lines):
    """{``<cylinder>:<phase>``: ns} of the idle stretches ``gap_list``
    [(t0, t1)]: each goes to the shortest phase, of any thread, that covers
    its middle; under ``None`` those that no phase covers."""
    out = {}
    for s, e in gap_list:
        mid = 0.5 * (s + e)
        best = None
        for nested in nested_lines:
            k = innermost(nested, mid)
            if k >= 0:
                cyl, name, t0, t1, _ = nested[k]
                if best is None or t1 - t0 < best[0]:
                    best = (t1 - t0, f"{cyl}:{name}")
        label = None if best is None else best[1]
        out[label] = out.get(label, 0.0) + (e - s)
    return out


def whole_turns(marks, t0, t1, clear):
    """(start_ns, end_ns, iterations) as ``tracered.iteration_span`` gives
    them for the hub boundaries ``marks`` [(t_ns, iteration)] inside [t0,
    t1], but starting at the first boundary at or after ``clear`` that a hub
    step of the same size follows as follows the slice's first boundary
    (one iteration, or a megastep window): the hub's cycle has one very
    long iteration in it, so that only whole turns of it compare.  With
    ``clear`` at or before the first boundary that is the first boundary
    itself.  ``None`` where no such boundary has an iteration after it."""
    inside = sorted(m for m in marks if t0 <= m[0] <= t1)
    bounds = []                       # one per boundary: its first mark
    for t, it in inside:
        if not bounds or bounds[-1][1] != it:
            bounds.append((t, it))
    steps = [b[1] - a[1] for a, b in zip(bounds, bounds[1:])]
    for k, step in enumerate(steps):
        if bounds[k][0] >= clear and step == steps[0]:
            n = sum(max(0, d) for d in steps[k:])
            return (bounds[k][0], inside[-1][0], n) if n > 0 else None
    return None


def reduce(loaded):
    """The numbers of one trace, or ``None`` where it holds no device run
    or no ``tpusppy:`` phase (a CPU run; a program without phases)."""
    lines = loaded["lines"]
    runs = {p: evs for p, evs in loaded["runs"].items() if evs}
    nested_lines = [nest(rec["phases"]) for rec in lines if rec["phases"]]
    if not runs or not nested_lines:
        return None
    marks = [m for rec in lines for m in rec["marks"]]
    edge = {name: (s, e) for name, s, e in marks
            if name in (tracered.START, tracered.STOP)}
    if len(edge) == 2:
        t0, t1 = edge[tracered.START][1], edge[tracered.STOP][0]
    else:
        t0 = min(s for evs in runs.values() for _, s, _, _ in evs)
        t1 = max(e for evs in runs.values() for _, _, e, _ in evs)
    n = len(runs)
    joins = {p: join(lines, evs) for p, evs in runs.items()}
    # runs that were launched before the trace began cannot be joined: the
    # iterations are counted from the first boundary behind the last of them
    clear = max([j[2] for joined in joins.values() for j in joined
                 if j[3] == BEFORE and j[2] > t0 and j[1] < t1], default=t0)
    span = whole_turns(
        [(s, int(name[len(tracered.ITER):])) for name, s, _ in marks
         if name.startswith(tracered.ITER)], t0, t1, clear)
    slice_ns, iter_ns, idle_ns = {}, {}, {}
    n_runs = n_unjoined = n_before = 0
    for p, evs in runs.items():
        joined = joins[p]
        inside = [j for j in joined if j[2] > t0 and j[1] < t1]
        n_runs += len(inside)
        n_unjoined += sum(j[3] is None for j in inside)
        n_before += sum(j[3] == BEFORE for j in inside)
        _add(slice_ns, device_time(joined, t0, t1), 1.0 / n)
        if span is not None:
            _add(iter_ns, device_time(joined, span[0], span[1]), 1.0 / n)
        gap_list = tracered.gaps(
            tracered.union((s, e) for _, s, e, _ in evs), t0, t1)
        for label, ns in idle_by_phase(gap_list, nested_lines).items():
            idle_ns[label] = idle_ns.get(label, 0.0) + ns / n
    return {
        "window_s": (t1 - t0) / 1e9,
        "runs": n_runs, "unjoined": n_unjoined, "before_slice": n_before,
        "device_s": _seconds(slice_ns),
        "iterations": None if span is None else {
            "count": span[2], "span_s": (span[1] - span[0]) / 1e9,
            "device_s": _seconds(iter_ns)},
        "idle_s": sum(idle_ns.values()) / 1e9,
        "idle_unexplained_s": idle_ns.get(None, 0.0) / 1e9,
        "idle_by_phase_s": dict(tracered.top(
            (str(k), v) for k, v in idle_ns.items() if k is not None)),
    }


def _add(total, part, weight):
    for cyl, by in part.items():
        into = total.setdefault(cyl, {})
        for phase, ns in by.items():
            into[phase] = into.get(phase, 0.0) + weight * ns


def _seconds(by_cyl):
    return {str(cyl): {str(ph): ns / 1e9 for ph, ns in by.items()}
            for cyl, by in by_cyl.items()}


# -- what the readers call ------------------------------------------------------

def of(obs):
    """The reduction for the traced run ``obs`` (what a driver returned),
    computed at the first call and printed once to standard error; ``None``
    for an untraced run, a run without a chip, or a trace without phases."""
    if _KEY not in obs:
        obs[_KEY] = None
        tracer = obs.get("tracer")
        xspace = getattr(tracer, "_xspace", None)
        if obs.get("trace") is not None and xspace is not None:
            import jax

            obs[_KEY] = reduce(load(
                jax.profiler.ProfileData.from_serialized_xspace(xspace)))
            print("progtrace " + json.dumps(obs[_KEY]), file=sys.stderr,
                  flush=True)
    return obs[_KEY]


def device_ms_per_iter(obs, cylinders):
    """Device milliseconds per hub iteration, over the whole turns of the
    hub's cycle that :func:`reduce` counts, of the runs that threads of
    ``cylinders`` (a name, or a pattern as ``spoke*``) launched."""
    red = of(obs)
    if red is None or red["iterations"] is None:
        return None
    secs = sum(sum(by.values())
               for cyl, by in red["iterations"]["device_s"].items()
               if fnmatch.fnmatchcase(cyl, cylinders))
    return 1e3 * secs / red["iterations"]["count"]


def phase_counter(obs, pattern, field):
    """Sum of the window's ``phase.<cylinder>.<name>.<field>`` registry
    deltas, ``pattern`` being ``<cylinder>.<name>`` with ``*`` for every
    cylinder or ``spoke*`` for the spokes; ``None`` where the program has
    no such counter."""
    want = f"phase.{pattern}.{field}"
    found = [v for k, v in obs["counters"].items()
             if fnmatch.fnmatchcase(k, want)]
    return sum(found) if found else None


def phase_mean_s(obs, pattern):
    """Mean seconds of one phase in the window: its ``secs`` over its
    ``count``."""
    secs = phase_counter(obs, pattern, "secs")
    count = phase_counter(obs, pattern, "count")
    return secs / count if secs is not None and count else None


def phase_per_request_s(obs, pattern):
    """Seconds of one phase in the window over the window's requests."""
    secs = phase_counter(obs, pattern, "secs")
    return secs / len(obs["requests"]) if secs is not None \
        and obs["requests"] else None
