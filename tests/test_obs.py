"""Observability subsystem (tpusppy.obs): trace ring, Perfetto export,
metrics registry absorption, report arrays, logger fold.

The disabled-path guard here is the contract that lets instrumentation
live in hot paths permanently: tracing off must mean zero events, a
shared no-op span singleton, and a pinned (loose, but bounding) per-call
overhead.
"""

import json
import threading
import time

import numpy as np
import pytest

from tpusppy.obs import log as obs_log
from tpusppy.obs import metrics, perfetto, report, trace
from tpusppy.solvers import hostsync


# ---------------------------------------------------------------------------
# trace ring buffer
# ---------------------------------------------------------------------------

def test_ring_overflow_keeps_newest():
    buf = trace.TraceBuffer(capacity=8)
    for i in range(20):
        buf.add(trace.Event(float(i), 0, "t", f"e{i}", "instant", None,
                            None))
    evs = buf.snapshot()
    assert len(evs) == 8
    assert buf.dropped == 12
    assert [e.name for e in evs] == [f"e{i}" for i in range(12, 20)]


def test_spans_nest_and_carry_payload():
    trace.enable()
    with trace.span("hub", "outer", k=1) as sp:
        time.sleep(0.002)
        with trace.span("hub", "inner"):
            time.sleep(0.001)
        sp.add(late=True)
    evs = [e for e in trace.events() if e.kind == "span"]
    assert [e.name for e in evs] == ["inner", "outer"]  # exit order
    inner, outer = evs
    # nesting: inner's window sits inside outer's
    assert outer.t <= inner.t
    assert inner.t + inner.dur <= outer.t + outer.dur + 1e-9
    assert outer.payload == {"k": 1, "late": True}


def test_ring_thread_safety_under_writer_storm():
    trace.enable(capacity=4096)
    n_threads, per_thread = 4, 3000
    errs = []

    def storm(tid):
        try:
            for i in range(per_thread):
                if i % 3 == 0:
                    with trace.span("storm", f"s{tid}"):
                        pass
                elif i % 3 == 1:
                    trace.instant("storm", f"i{tid}", i=i)
                else:
                    trace.counter("storm", f"c{tid}", i)
        except Exception as e:                      # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=storm, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    evs = trace.events()
    assert len(evs) == 4096                  # ring full, newest kept
    assert len(evs) + trace.dropped() == n_threads * per_thread
    assert all(isinstance(e, trace.Event) for e in evs)


def test_disabled_path_guard():
    """Tracing off: zero events, a SHARED no-op singleton (no per-call
    span allocation), and pinned overhead."""
    assert not trace.enabled()       # autouse fixture disables
    with trace.span("hub", "x", payload=1):
        pass
    trace.instant("hub", "y", a=2)
    trace.counter("hub", "z", 3.0)
    trace.record_span("hub", "w", 0.0, 1.0, {"big": "dict"})
    assert trace.events() == []
    # singleton identity — the disabled path allocates no span object
    # (and therefore no internal payload dict / Event tuple)
    assert trace.span("a", "b") is trace.span("c", "d")
    n = 200_000
    t0 = time.perf_counter()
    for _ in range(n):
        with trace.span(None, "noop"):
            pass
    dt = time.perf_counter() - t0
    # generous absolute pin (~5us/call budget): catches an accidentally
    # always-on path (ring append ~20x this) without flaking on slow CI
    assert dt < n * 5e-6, f"disabled span path too slow: {dt / n * 1e9:.0f}ns"
    assert trace.events() == []


def test_disabled_path_guard_with_request_context():
    """The SAME <5us/span pin with the telemetry plane's request context
    active: a bound request scope must not push the disabled fast path
    past its budget, and the tenant-* helpers must allocate nothing."""
    from tpusppy.obs import telemetry

    assert not trace.enabled()
    with telemetry.request_scope("tr-abc", "req-1"):
        # disabled tenant helpers: no events, the shared span singleton
        telemetry.tenant_instant(None, None, "x", a=1)
        telemetry.tenant_counter(None, None, "rel_gap", 0.5)
        assert telemetry.tenant_span(None, None, "s") is trace._NULL
        assert trace.events() == []
        n = 200_000
        t0 = time.perf_counter()
        for _ in range(n):
            with telemetry.tenant_span(None, None, "noop"):
                pass
        dt = time.perf_counter() - t0
        assert dt < n * 5e-6, (f"disabled tenant-span path too slow: "
                               f"{dt / n * 1e9:.0f}ns")
    assert trace.events() == []


# ---------------------------------------------------------------------------
# Perfetto export
# ---------------------------------------------------------------------------

def _make_doc():
    trace.enable()
    with trace.span("hub", "iter", k=1):
        with trace.span("hub", "solve"):
            pass
    trace.instant("dispatch", "segment", seg_f=8)
    trace.counter("hub", "rel_gap", 0.25)
    with trace.span("spoke1:Lagrangian", "bound_pass"):
        pass
    return perfetto.export(trace.events())


def test_perfetto_schema_sanity(tmp_path):
    doc = _make_doc()
    # loadable: a strict JSON round-trip
    path = tmp_path / "t.perfetto.json"
    with open(path, "w") as f:
        json.dump(doc, f)
    doc2 = json.loads(path.read_text())
    evs = doc2["traceEvents"]
    body = [e for e in evs if e["ph"] != "M"]
    ts = [e["ts"] for e in body]
    assert ts == sorted(ts), "timestamps must be monotone"
    # matched B/E pairs per thread row, properly nested
    stacks = {}
    for e in body:
        if e["ph"] == "B":
            stacks.setdefault(e["tid"], []).append(e["name"])
        elif e["ph"] == "E":
            assert stacks.get(e["tid"]), "E without matching B"
            stacks[e["tid"]].pop()
    assert all(not s for s in stacks.values()), "unclosed B events"
    # named thread rows exist for every logical track
    names = {e["args"]["name"] for e in evs if e["ph"] == "M"}
    assert {"hub", "dispatch", "spoke1:Lagrangian"} <= names
    # counters carry values
    cs = [e for e in body if e["ph"] == "C"]
    assert cs and cs[0]["args"]["value"] == 0.25


def test_perfetto_nonfinite_payloads_stay_strict_json(tmp_path):
    """The hub's FIRST bound update carries old=±inf by construction;
    json.dump would emit bare Infinity tokens (invalid JSON) and
    ui.perfetto.dev's JSON.parse would reject the whole artifact."""
    trace.enable()
    trace.instant("hub", "outer_bound_update", old=float("-inf"),
                  new=-110.0, worst=float("nan"))
    path = tmp_path / "inf.perfetto.json"
    perfetto.export(trace.events(), path=str(path))
    text = path.read_text()
    # strict parse (Python's json.loads is lenient about Infinity/NaN —
    # check the raw text instead)
    assert "Infinity" not in text and "NaN" not in text
    ev = [e for e in json.loads(text)["traceEvents"]
          if e.get("name") == "outer_bound_update"][0]
    assert ev["args"]["old"] == "-inf" and ev["args"]["new"] == -110.0


# ---------------------------------------------------------------------------
# metrics registry + hostsync absorption
# ---------------------------------------------------------------------------

def test_registry_absorption_parity_with_tracker():
    """host_sync_count via the registry window == the legacy thread-local
    tracker over the same measured window (what bench's per-segment
    fields are now sourced from)."""
    with metrics.window() as win, hostsync.track() as tr:
        for i in range(7):
            hostsync.fetch(np.arange(4.0), overlapped=(i % 2 == 1))
    assert int(win.delta("host_sync.count")) == tr.count == 7
    assert int(win.delta("host_sync.overlapped")) == tr.overlapped == 3
    assert win.delta("host_sync.blocked_secs") == pytest.approx(
        tr.blocked_secs, rel=1e-9)
    assert win.delta("host_sync.fetch_secs") == pytest.approx(
        tr.fetch_secs, rel=1e-9)
    # and the window is a DELTA view: a second window starts clean
    with metrics.window() as win2:
        hostsync.fetch(np.zeros(2))
    assert int(win2.delta("host_sync.count")) == 1


def test_registry_reset_keeps_module_bound_counters_live():
    """reset() must zero in place: instrumented modules bind counter
    objects at import (hostsync._CTR_COUNT) — dropping them would fork
    the registry and absorption would silently go stale."""
    hostsync.fetch(np.zeros(2))
    assert metrics.value("host_sync.count") >= 1
    metrics.reset()
    assert metrics.value("host_sync.count") == 0
    hostsync.fetch(np.zeros(2))
    assert metrics.value("host_sync.count") == 1


def test_hostsync_reset_clears_leaked_trackers():
    """A tracker left open (failed test, missing finally) must stop
    counting once reset() runs — the conftest autouse fixture calls it
    so counts can never bleed across tests."""
    t = hostsync.SyncTracker()
    hostsync._stack().append(t)     # leak it deliberately
    hostsync.reset()
    hostsync.fetch(np.zeros(2))
    assert t.count == 0


def test_histogram_and_gauge():
    h = metrics.histogram("h.test")
    for v in (1.0, 3.0, 2.0):
        h.add(v)
    assert h.summary() == {"count": 3, "total": 6.0, "min": 1.0,
                           "max": 3.0, "p50": 2.0, "p95": 3.0, "p99": 3.0}
    metrics.gauge("g.test").set(4.5)
    d = metrics.dump()
    assert d["g.test"] == 4.5 and d["h.test"]["count"] == 3
    # window deltas over a histogram are WINDOW totals, not lifetime
    with metrics.window() as win:
        h.add(5.0)
    assert win.delta("h.test") == 5.0


def test_histogram_quantiles_reservoir():
    """Latency percentiles (serving SLOs): exact nearest-rank while the
    stream fits the reservoir, sampled (still order-of-magnitude right)
    past it, and reset restores determinism."""
    h = metrics.histogram("h.quant")
    for v in range(1, 101):
        h.add(float(v))
    s = h.summary()
    assert s["p50"] == pytest.approx(50.0, abs=1.0)
    assert s["p95"] == pytest.approx(95.0, abs=1.0)
    assert s["p99"] == pytest.approx(99.0, abs=1.0)
    # overflow the reservoir: quantiles stay sane under sampling
    for v in range(101, 5001):
        h.add(float(v))
    s = h.summary()
    assert len(h._samples) == metrics.Histogram.RESERVOIR_CAP
    assert 1500.0 < s["p50"] < 3500.0
    assert s["p99"] > 4000.0
    # deterministic across identical insert streams
    h.reset()
    for v in range(1, 101):
        h.add(float(v))
    assert h.summary()["p50"] == pytest.approx(50.0, abs=1.0)
    assert h.summary()["count"] == 100


def test_span_open_across_disable_is_dropped():
    """A span still open when tracing is disabled/reset (lingering daemon
    cylinder thread) must not leak its event into the next owner's ring."""
    trace.enable()
    sp = trace.span("hub", "stale")
    sp.__enter__()
    trace.disable()
    trace.reset()
    trace.enable()
    sp.__exit__(None, None, None)
    assert [e.name for e in trace.events()] == []


# ---------------------------------------------------------------------------
# report
# ---------------------------------------------------------------------------

def test_report_series_and_span_totals():
    trace.enable()
    for i, g in enumerate((0.5, 0.2, 0.05)):
        trace.counter("hub", "rel_gap", g)
        trace.counter("hub", "best_outer", -110.0 - i)
    with trace.span("hub", "ph_iter"):
        pass
    with trace.span("hub", "ph_iter"):
        pass
    trace.instant("dispatch", "speculation_discard", segments=1)
    rep = report.build_report(trace.events())
    assert [v for _, v in rep["gap_vs_wall"]] == [0.5, 0.2, 0.05]
    assert rep["gap_vs_wall"][-1][1] == 0.05          # ends at final gap
    assert len(rep["bounds_vs_wall"]["best_outer"]) == 3
    assert rep["tracks"]["hub"]["ph_iter"]["count"] == 2
    assert rep["instants"]["dispatch"]["speculation_discard"] == 1
    assert rep["dropped_events"] == 0
    json.dumps(rep)                                   # serializable
    # scoped variants: a counters override (per-segment window deltas)
    # and a pinned drop count survive verbatim — the live ring may have
    # moved on by the time a snapshot's report is built
    rep2 = report.build_report(trace.events(),
                               counters={"seg.only": 2.0}, dropped=5)
    assert rep2["counters"] == {"seg.only": 2.0}
    assert rep2["dropped_events"] == 5
    # Window.deltas: counters windowed, gauges current
    metrics.inc("w.count", 3)
    metrics.gauge("w.gauge").set(7.0)
    with metrics.window() as win:
        metrics.inc("w.count", 2)
    d = win.deltas()
    assert d["w.count"] == 2.0 and d["w.gauge"] == 7.0


# ---------------------------------------------------------------------------
# logger fold
# ---------------------------------------------------------------------------

def test_get_logger_track_format():
    import io
    import logging

    sink = io.StringIO()
    h = logging.StreamHandler(sink)
    h.setFormatter(obs_log._TrackFormatter())
    obs_log.root.addHandler(h)
    try:
        obs_log.get_logger("cylinders.hub").info("gap certified")
        obs_log.root.info("bare root line")
    finally:
        obs_log.root.removeHandler(h)
    out = sink.getvalue()
    assert "[cylinders.hub] gap certified" in out
    # the root logger renders untagged (global_toc-era output preserved)
    assert "\nbare root line" in "\n" + out
    # tpusppy.log compat surface still routes here
    import tpusppy.log as compat

    assert compat.get_logger is obs_log.get_logger
    assert compat.logger is obs_log.root


def test_log_level_knob():
    lg = obs_log.get_logger("lvl.test")
    try:
        obs_log.set_level("WARNING")
        assert not lg.isEnabledFor(20)   # INFO suppressed
        obs_log.set_level("DEBUG")
        assert lg.isEnabledFor(10)
    finally:
        obs_log.set_level("INFO")


# ---------------------------------------------------------------------------
# config wiring
# ---------------------------------------------------------------------------

def test_config_tracing_enables_and_flushes(tmp_path):
    from tpusppy.utils.config import Config

    cfg = Config()
    cfg.tracing_args()
    assert trace.maybe_enable_from_config(cfg) is False   # default off
    path = str(tmp_path / "run.perfetto.json")
    cfg["tracing"] = path
    assert trace.maybe_enable_from_config(cfg) is True
    trace.instant("hub", "mark")
    assert trace.flush(path) == path
    doc = json.loads(open(path).read())
    assert any(e.get("name") == "mark" for e in doc["traceEvents"])
    rep = json.loads(open(path + ".report.json").read())
    assert rep["n_events"] >= 1


# ---------------------------------------------------------------------------
# instrumented seams (cheap, no jax compiles)
# ---------------------------------------------------------------------------

def test_mailbox_counters_and_versioned_put_skips():
    from tpusppy.cylinders import Mailbox

    trace.enable()
    with metrics.window() as win:
        mb = Mailbox(2, name="t")
        mb.put(np.zeros(2))
        mb.put_versioned(("tok", 1), lambda: np.ones(2))
        mb.put_versioned(("tok", 1), lambda: np.ones(2))   # skip
        mb.get()
        mb.kill()
    assert int(win.delta("mailbox.puts")) == 2
    assert int(win.delta("mailbox.put_skips")) == 1
    assert int(win.delta("mailbox.gets")) == 1
    assert int(win.delta("mailbox.kills")) == 1
    names = {e.name for e in trace.events()}
    assert {"put", "put_skip", "kill"} <= names


def test_hub_bound_updates_and_termination_events():
    from tpusppy.cylinders.hub import Hub

    trace.enable()
    h = Hub.__new__(Hub)
    h.options = {"rel_gap": 1e-3}

    class _Opt:
        is_minimizing = True

    h.opt = _Opt()
    h.initialize_bound_values()
    h.outerbound_spoke_chars = {1: 'L'}
    h.innerbound_spoke_chars = {2: 'X'}
    h.last_gap = np.inf
    h.stalled_iter_cnt = 0
    h.OuterBoundUpdate(-110.0, idx=1)
    h.InnerBoundUpdate(-109.99, idx=2)
    assert h.determine_termination()
    evs = trace.events()
    names = [e.name for e in evs]
    assert "outer_bound_update" in names and "inner_bound_update" in names
    term = [e for e in evs if e.name == "terminate"]
    assert term and term[0].payload["reason"] == "rel_gap"
    assert term[0].payload["best_outer"] == -110.0
    rep = report.build_report(evs)
    assert rep["gap_vs_wall"][-1][1] == pytest.approx(0.01 / 110.0, rel=1e-6)
    assert metrics.value("hub.outer_bound_updates") == 1


def test_continue_frozen_dispatch_billing():
    """Serial + pipelined continuations bill segments/flops into the
    registry, and a stop verdict bills the discarded speculation."""
    from tpusppy.solvers import segmented

    class FakeSol:
        def __init__(self, v, iters):
            self.raw = v
            self.iters = np.array([iters])
            self.pri_res = np.array([v])
            self.dua_res = np.array([v])

    # serial: 3 dispatches exhaust the budget (never done)
    with metrics.window() as win:
        segmented.continue_frozen(
            lambda w: FakeSol(w * 0.5, 8), FakeSol(1.0, 8), 8, 24,
            all_done=lambda s: False, seg_flops=100.0)
    assert int(win.delta("dispatch.segments")) == 3
    assert win.delta("dispatch.flops") == 300.0
    assert int(win.delta("speculation.segments")) == 0

    # pipelined: incoming already-stopped iterate discards nothing;
    # a later stop with a spec segment in flight bills the discard
    calls = []

    def run_segment(w):
        calls.append(w)
        return FakeSol(w * 0.5, 4 if len(calls) >= 2 else 8)

    with metrics.window() as win:
        segmented.continue_frozen(
            run_segment, FakeSol(1.0, 8), 8, 80, pipeline=True,
            overlap=2, seg_flops=10.0)
    assert int(win.delta("speculation.discarded_segments")) >= 1
    assert win.delta("speculation.discarded_flops") == pytest.approx(
        10.0 * win.delta("speculation.discarded_segments"))
    # billing invariant: discarded <= speculative <= dispatched
    assert (win.delta("speculation.discarded_segments")
            <= win.delta("speculation.segments")
            <= win.delta("dispatch.segments"))

    # the PRODUCTION depth (overlap=1, the default): every steady-state
    # dispatch launches from the just-popped candidate before its
    # verdict fetch — that IS the overlap, and it must bill as
    # speculative (a stop with one in flight then discards 1 <= spec)
    calls2 = []

    def run_segment2(w):
        calls2.append(w)
        return FakeSol(w * 0.5, 4 if len(calls2) >= 3 else 8)

    with metrics.window() as win1:
        segmented.continue_frozen(
            run_segment2, FakeSol(1.0, 8), 8, 80, pipeline=True,
            check_incoming=True, seg_flops=10.0)
    assert win1.delta("speculation.segments") >= 1
    assert (win1.delta("speculation.discarded_segments")
            <= win1.delta("speculation.segments")
            <= win1.delta("dispatch.segments"))


@pytest.mark.slow
def test_wheel_trace_has_cylinder_tracks_and_final_gap(tmp_path,
                                                       monkeypatch):
    """The flight-recorder acceptance shape on a REAL (tiny) wheel: the
    trace shows >= 4 distinct tracks (hub, spoke, dispatch, host-sync)
    and the report's gap-vs-wall array ends at the reported final gap.

    Slow tier (new-test policy: >~5s, and thread-timing variable — spoke
    cold-start + linger put it anywhere from ~6 to ~25s); the cheap
    synthetic tests above cover the report/track logic in tier-1 and the
    nightly traced-bench job exercises this same path end to end."""
    import bench

    monkeypatch.setenv("BENCH_TRACE_DIR", str(tmp_path))
    trace.enable()
    ws_entry = bench.traced_farmer_wheel()
    assert "error" not in ws_entry
    dump = ws_entry["trace"]
    tracks = set(dump["report"]["tracks"]) | {
        t for t in dump["report"]["instants"]}
    assert "hub" in tracks
    assert any(t.startswith("spoke") for t in tracks)
    assert "dispatch" in tracks
    assert "host-sync" in tracks
    assert len(tracks) >= 4
    gvw = dump["report"]["gap_vs_wall"]
    assert gvw and gvw[-1][1] == pytest.approx(ws_entry["rel_gap"])
    # perfetto artifact exists and is loadable
    doc = json.loads(open(dump["path"]).read())
    assert doc["traceEvents"]


# ---------------------------------------------------------------------------
# phases: one call site, three sinks (profiler trace, registry, ring)
# ---------------------------------------------------------------------------

def test_phase_feeds_the_registry_with_the_ring_off():
    assert not trace.enabled()
    trace.set_thread_track("spoke1:LagrangianOuterBound")
    try:
        with metrics.window() as win:
            for _ in range(3):
                with trace.phase("pass", k=1) as ph:
                    time.sleep(0.001)
                    ph.add(late=True)       # payload is the ring's: a no-op
    finally:
        trace.set_thread_track(None)
    # the cylinder is the track up to its first ':'
    assert win.delta("phase.spoke1.pass.count") == 3
    assert 0.003 <= win.delta("phase.spoke1.pass.secs") < 0.5
    assert trace.events() == []
    assert trace.cylinder() == "main"


def test_phase_records_a_ring_span_with_payload_when_on():
    trace.enable()
    trace.set_thread_track("hub")
    try:
        with trace.phase("megastep", n_live=15) as ph:
            with trace.phase("fetchlike"):
                pass
            ph.add(iters=15)
    finally:
        trace.set_thread_track(None)
    evs = [e for e in trace.events() if e.kind == "span"]
    assert [(e.track, e.name) for e in evs] == [("hub", "fetchlike"),
                                                 ("hub", "megastep")]
    assert evs[1].payload == {"n_live": 15, "iters": 15}
    assert evs[0].payload is None
    assert evs[1].t <= evs[0].t and evs[0].dur <= evs[1].dur
    # ... and the registry all the same
    assert metrics.value("phase.hub.megastep.count") == 1
    assert metrics.value("phase.hub.megastep.secs") == pytest.approx(
        evs[1].dur)


def test_phase_open_across_disable_keeps_counting_but_drops_its_event():
    trace.enable()
    ph = trace.phase("stale")
    ph.__enter__()
    trace.disable()
    trace.reset()
    trace.enable()
    ph.__exit__(None, None, None)
    assert trace.events() == []
    assert metrics.value("phase.main.stale.count") == 1


def test_phase_off_path_cost_is_pinned():
    """The ring off and no profiler session: a phase is an annotation that
    checks one flag, two registry adds and two clock reads.  Best of five
    batches, so that a loaded machine does not flake it; the pin catches a
    path that rebuilds its names or rebinds jax at every call."""
    assert not trace.enabled()
    with trace.phase("warm"):           # binds jax.profiler, once
        pass
    assert trace._annotation_cls is not None
    n, best = 20_000, float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(n):
            with trace.phase("noop"):
                pass
        best = min(best, (time.perf_counter() - t0) / n)
    assert best < 10e-6, f"phase() off path too slow: {best * 1e9:.0f}ns"
    assert metrics.value("phase.main.noop.count") == 5 * n
    assert trace.events() == []


def _on_track(track, fn):
    def run():
        trace.set_thread_track(track)
        fn()

    return threading.Thread(target=run, name=track)


def test_per_cylinder_host_sync_counters_sum_to_the_process_wide():
    def fetches(k):
        def go():
            for i in range(k):
                hostsync.fetch(np.arange(4.0), overlapped=(i % 3 == 0))
        return go

    with metrics.window() as win:
        threads = [_on_track("hub", fetches(7)),
                   _on_track("spoke1:LagrangianOuterBound", fetches(5))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
        hostsync.fetch(np.zeros(2))                     # this thread: main
    d = win.deltas()
    assert (d["host_sync.count.hub"], d["host_sync.count.spoke1"],
            d["host_sync.count.main"]) == (7, 5, 1)
    by = [k for k in d if k.startswith("host_sync.count.")]
    assert sum(d[k] for k in by) == d["host_sync.count"] == 13
    # (the registry keeps other tests' cylinders, zeroed: they add nothing)
    blocked = [k for k in d if k.startswith("host_sync.blocked_secs.")]
    assert {k for k in blocked if d[k] > 0} == {
        "host_sync.blocked_secs." + c for c in ("hub", "spoke1", "main")}
    assert sum(d[k] for k in blocked) == pytest.approx(
        d["host_sync.blocked_secs"], rel=1e-9)
    # an overlapped fetch blocks nobody: counted, not billed
    assert d["host_sync.overlapped"] == 3 + 2


def test_phases_reach_the_profiler_trace_on_their_own_threads_lines(tmp_path):
    """Under a ``jax.profiler`` session (CPU here) two named threads'
    phases, and a fetch, stand as ``tpusppy:<cylinder>:<name>`` on two
    different lines of the written XSpace."""
    import glob

    import jax

    both = threading.Barrier(2, timeout=60)

    def work(name):
        def go():
            both.wait()       # alive together: the OS gives a finished
            for _ in range(3):                  # thread's id, and so its
                with trace.phase(name):         # line, to the next one
                    hostsync.fetch(np.zeros(2))
            both.wait()
        return go

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        threads = [_on_track("hub", work("megastep")),
                   _on_track("spoke2:XhatShuffleInnerBound", work("pass"))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    lines = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            names = {ev.name for ev in line.events
                     if ev.name.startswith(trace.ANNOTATION_PREFIX)}
            if names:
                lines.append(names)
    assert sorted(lines, key=sorted) == [
        {"tpusppy:hub:megastep", "tpusppy:hub:fetch"},
        {"tpusppy:spoke2:pass", "tpusppy:spoke2:fetch"}]


def test_rescue_rows_counts_on_a_tiny_wheel_whose_rescue_fires():
    """A hub-only wheel whose Iter0 sweeps are cut short: every row misses
    ``straggler_tol`` and is re-solved on the host, which ``rescue.rows``
    counts and the ``rescue`` phase times; the cap's leftovers are counted
    apart."""
    from tpusppy.cylinders import PHHub
    from tpusppy.models import farmer
    from tpusppy.opt.ph import PH
    from tpusppy.spin_the_wheel import WheelSpinner

    S = 5
    options = {"defaultPHrho": 1.0, "PHIterLimit": 1, "convthresh": -1.0,
               "straggler_lp_max": 3,
               "solver_options": {"max_iter": 5, "polish": False}}
    hub = {"hub_class": PHHub, "hub_kwargs": {"options": {}},
           "opt_class": PH,
           "opt_kwargs": {"options": options,
                          "all_scenario_names":
                              farmer.scenario_names_creator(S),
                          "scenario_creator": farmer.scenario_creator,
                          "scenario_creator_kwargs": {"num_scens": S}}}
    with metrics.window() as win:
        ws = WheelSpinner(hub, []).run()
    d = win.deltas()
    pri = np.asarray(ws.opt.pri_res)
    assert d["phase.hub.rescue.count"] >= 1
    assert d["rescue.rows"] >= 3                    # Iter0's, at the cap
    assert d["rescue.left_at_batch"] >= S - 3
    assert d["rescue.rows"] + d["rescue.left_at_batch"] >= S
    assert np.isfinite(pri).all()
    # the wheel's own phases, on the tracks the spinner gives its threads
    for key in ("phase.main.build", "phase.hub.iter0", "phase.hub.refresh",
                "phase.hub.xbar", "phase.hub.w_update",
                "phase.main.teardown"):
        assert d[key + ".count"] >= 1 and d[key + ".secs"] > 0, key
    assert d["phase.hub.rescue.secs"] <= d["phase.hub.iter0.secs"]


# ---------------------------------------------------------------------------
# outcomes: what a solve spent and how it ended (registry only)
# ---------------------------------------------------------------------------

def test_outcome_counters_land_under_the_calling_threads_cylinder():
    def attempts(k, accepted):
        def go():
            for _ in range(k):
                trace.outcome("frozen", count=1, sweeps=250, budget=1000,
                              accepted=accepted)
        return go

    with metrics.window() as win:
        threads = [_on_track("hub", attempts(3, 1)),
                   _on_track("spoke1:LagrangianOuterBound", attempts(2, 0))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
        trace.outcome("refresh", rows=4, cold=1)            # this thread
    d = win.deltas()
    got = {k: v for k, v in d.items() if k.startswith("solve.") and v}
    assert got == {
        "solve.hub.frozen.count": 3, "solve.hub.frozen.sweeps": 750,
        "solve.hub.frozen.budget": 3000, "solve.hub.frozen.accepted": 3,
        "solve.spoke1.frozen.count": 2, "solve.spoke1.frozen.sweeps": 500,
        "solve.spoke1.frozen.budget": 2000,
        "solve.main.refresh.rows": 4, "solve.main.refresh.cold": 1}
    # a field passed as 0 has its counter all the same: a dump shows it
    assert d["solve.spoke1.frozen.accepted"] == 0
    # registry only: no ring event, with the ring on either
    trace.enable()
    trace.outcome("frozen", count=1)
    assert trace.events() == []


def test_outcome_off_path_cost_is_pinned():
    """One solve's whole record at S=1000: the two counts over the rows'
    masks and a call with every field of the ``frozen`` kind.  Best of
    five batches, as for the phase; the pin catches a call that rebuilds
    its counter names or takes the registry's lock to find them."""
    assert not trace.enabled()
    S = 1000
    done = np.arange(S) % 7 != 0
    in_tol = np.arange(S) % 5 != 0
    n, best = 5_000, float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(n):
            trace.outcome(
                "frozen", count=1, sweeps=1000, budget=1000, rows=S,
                rows_done=int(np.count_nonzero(done)),
                rows_in_tol=int(np.count_nonzero(in_tol)), accepted=0)
        best = min(best, (time.perf_counter() - t0) / n)
    assert best < 20e-6, f"outcome() too slow: {best * 1e9:.0f}ns"
    assert metrics.value("solve.main.frozen.count") == 5 * n
    assert metrics.value("solve.main.frozen.rows_done") == \
        5 * n * np.count_nonzero(done)


def _tiny_solution(engine):
    """A (4, n) batch of either engine, its sweeps cut short."""
    from tpusppy.ir import ScenarioBatch
    from tpusppy.models import farmer, sslp
    from tpusppy.solvers import admm, shared_admm

    st = admm.ADMMSettings(max_iter=20, restarts=1, polish=False)
    if engine == "dense":
        names = farmer.scenario_names_creator(4)
        b = ScenarioBatch.from_problems(
            [farmer.scenario_creator(nm, num_scens=4) for nm in names])
        assert getattr(b, "A_shared", None) is None
        return admm.solve_batch(b.c, b.q2, b.A, b.cl, b.cu, b.lb, b.ub,
                                settings=st)
    names = sslp.scenario_names_creator(4)
    b = ScenarioBatch.from_problems(
        [sslp.scenario_creator(nm) for nm in names])
    assert b.A_shared is not None
    return shared_admm.solve_shared(b.c, b.q2, b.A_shared, b.cl, b.cu, b.lb,
                                    b.ub, settings=st)


@pytest.mark.parametrize("engine", ["dense", "shared"])
@pytest.mark.parametrize("vote", [None, (0, 0, 0, 0), (1, 0, 1, 1),
                                  (1, 1, 1, 1)],
                         ids=["own", "none", "some", "all"])
def test_measure_pack_round_trips_n_done(engine, vote):
    """The packed measurement carries the program's count of rows done
    beside its vote, for the dense and the shared engine's solutions:
    the solve's own vote, and each way a vote can fall."""
    import jax.numpy as jnp

    from tpusppy.solvers import admm

    sol = _tiny_solution(engine)
    if vote is not None:
        sol = sol._replace(done=jnp.asarray(vote, bool))
    S, n = sol.x.shape
    vec = np.asarray(admm.measure_pack(sol))
    assert vec.shape == (S * (n + 2) + 6,)
    meas = admm.measure_unpack(vec, S, n)
    # how much of the batch was still being swept: a batch this small has
    # no narrower rung, and the shared engine's loop has none at any size
    assert meas["narrow_sweeps"] == 0
    assert meas["row_sweeps"] == meas["full_row_sweeps"] == 20 * S
    done = np.asarray(sol.done)
    assert meas["n_done"] == int(done.sum())
    assert meas["all_done"] == bool(done.all())
    assert (meas["n_done"] == S) == meas["all_done"]
    np.testing.assert_array_equal(meas["x"], np.asarray(sol.x))
    np.testing.assert_array_equal(meas["pri"], np.asarray(sol.pri_res))
    np.testing.assert_array_equal(meas["dua"], np.asarray(sol.dua_res))
    assert meas["iters"] == int(np.asarray(sol.iters).max()) == 20


_REASONS = ("cold", "signature", "age", "declined")


def _solve_kinds(d):
    """{(cylinder, kind): {field: value}} of a window's ``solve.*`` deltas,
    for the kinds the window counted (the registry keeps other tests'
    counters, zeroed).  How often a kind happened stands under ``n``: a
    frozen attempt counts itself, a refresh solve is one of its four
    reasons and one run of the phase ``refresh``, a megastep iteration is
    one of ``dispatch.mega_iterations`` (the hub's, or ``main``'s where a
    hub runs alone)."""
    out = {}
    for k, v in d.items():
        parts = k.split(".")
        if parts[0] == "solve" and len(parts) == 4:
            out.setdefault((parts[1], parts[2]), {})[parts[3]] = v
    for (cyl, kind), f in out.items():
        f["n"] = (f["count"] if kind == "frozen"
                  else sum(f[r] for r in _REASONS) if kind == "refresh"
                  else d.get("dispatch.mega_iterations", 0) if f["budget"]
                  else 0)
    return {key: f for key, f in out.items() if f["n"]}


@pytest.mark.parametrize("mega", [0, 1], ids=["megastep", "legacy"])
def test_hub_outcomes_and_one_fetch_a_solve(mega):
    """A hub alone, 20 iterations: every solve and every window leaves its
    outcome, and fetches what it fetched before the outcomes were there:
    one packed vector a solve, one a window, one at the end."""
    from tpusppy.models import farmer
    from tpusppy.opt.ph import PH

    options = {"defaultPHrho": 1.0, "PHIterLimit": 20, "convthresh": -1.0,
               "display_progress": False, "solver_refresh_every": 4,
               "solver_options": {"megastep": mega}}
    with metrics.window() as win:
        ph = PH(options, farmer.scenario_names_creator(3),
                farmer.scenario_creator,
                scenario_creator_kwargs={"num_scens": 3})
        ph.ph_main()
    d = win.deltas()
    kinds = _solve_kinds(d)
    assert set(kinds) == ({("main", "refresh"), ("main", "mega")} if mega == 0
                          else {("main", "refresh"), ("main", "frozen")})
    ref = kinds["main", "refresh"]
    fro = kinds.get(("main", "frozen"), {"n": 0, "accepted": 0})
    meg = kinds.get(("main", "mega"), {"n": 0, "budget": 0})
    # the four reasons are the refresh solves, one run of the phase each
    assert ref["n"] == d["phase.main.refresh.count"] >= 2
    assert fro["n"] == d.get("phase.main.frozen.count", 0)
    assert meg["budget"] == 1000 * d["dispatch.mega_iterations"]
    # Iter0 and twenty iterations, each solved once, or once more where a
    # frozen attempt was thrown away
    assert meg["n"] + fro["accepted"] + ref["n"] == 21
    assert fro["n"] == fro["accepted"] + ref["declined"]
    assert ref["cold"] == 1                          # Iter0
    assert ref["signature"] == 1                     # the prox term arrives
    assert d["host_sync.count"] == (fro["n"] + ref["n"]
                                    + d["dispatch.megasteps"] + 1)
    assert d.get("rescue.rows", 0) == 0
    # the numbers the parent's program fetched on this run (PR 43)
    assert d["host_sync.count"] == (13 if mega == 0 else 23)


def test_a_segmented_solve_reports_no_sweeps(monkeypatch):
    """A shape that ``segmented`` splits into several dispatches fetches
    its LAST dispatch's iteration counter: such a solve leaves its rows,
    its reason and whether it was kept, and neither sweeps nor a budget to
    hold them against."""
    from tpusppy.models import farmer
    from tpusppy.opt.ph import PH
    from tpusppy.solvers import segmented

    # a model throughput of one flop a second: every cap lands on its floor
    monkeypatch.setattr(segmented, "_DISPATCH_EFF_FLOPS", 1.0)
    monkeypatch.setattr(segmented, "_DISPATCH_EFF_FLOPS_DENSE", 1.0)
    options = {"defaultPHrho": 1.0, "PHIterLimit": 6, "convthresh": -1.0,
               "display_progress": False, "solver_refresh_every": 4,
               "solver_options": {"megastep": 1}}
    with metrics.window() as win:
        PH(options, farmer.scenario_names_creator(3), farmer.scenario_creator,
           scenario_creator_kwargs={"num_scens": 3}).ph_main()
    d = win.deltas()
    assert d["dispatch.segmented_solves"] == (
        d["phase.main.refresh.count"] + d["phase.main.frozen.count"])
    kinds = _solve_kinds(d)
    ref, fro = kinds["main", "refresh"], kinds["main", "frozen"]
    assert ref["n"] + fro["accepted"] == 7 and fro["n"] >= 1
    for f in (ref, fro):
        assert f["rows"] == 3 * f["n"] and f["rows_done"] <= f["rows"]
        assert f.get("sweeps", 0) == f.get("budget", 0) == 0


def test_wheel_outcomes_add_up_per_cylinder():
    """PH hub + Lagrangian + XhatShuffle on a three-scenario farmer: what
    each cylinder's solves say of themselves adds up."""
    from tpusppy.cylinders import (LagrangianOuterBound, PHHub,
                                   XhatShuffleInnerBound)
    from tpusppy.models import farmer
    from tpusppy.opt.ph import PH
    from tpusppy.phbase import PHBase
    from tpusppy.spin_the_wheel import WheelSpinner
    from tpusppy.xhat_eval import Xhat_Eval

    def opt_kwargs():
        return {"options": {"defaultPHrho": 1.0, "PHIterLimit": 24,
                            "convthresh": -1.0, "solver_refresh_every": 4,
                            "xhat_looper_options": {"scen_limit": 3}},
                "all_scenario_names": farmer.scenario_names_creator(3),
                "scenario_creator": farmer.scenario_creator,
                "scenario_creator_kwargs": {"num_scens": 3}}

    hub = {"hub_class": PHHub,
           "hub_kwargs": {"options": {"rel_gap": 1e-9, "linger_secs": 5.0}},
           "opt_class": PH, "opt_kwargs": opt_kwargs()}
    spokes = [{"spoke_class": LagrangianOuterBound, "spoke_kwargs": {},
               "opt_class": PHBase, "opt_kwargs": opt_kwargs()},
              {"spoke_class": XhatShuffleInnerBound, "spoke_kwargs": {},
               "opt_class": Xhat_Eval, "opt_kwargs": opt_kwargs()}]
    with metrics.window() as win:
        WheelSpinner(hub, spokes).run()
    d = win.deltas()
    kinds = _solve_kinds(d)
    cylinders = {c for c, _ in kinds}
    assert {"hub", "spoke1", "spoke2"} <= cylinders <= {
        "hub", "spoke1", "spoke2", "main"}
    for (cyl, kind), f in kinds.items():
        assert 0 <= f["sweeps"] <= f["budget"], (cyl, kind, f)
        if kind != "mega":
            assert 0 <= f["rows_done"] <= f["rows"] == 3 * f["n"]
            assert 0 <= f["rows_in_tol"] <= f["rows"]
            # a solve, a phase: the seconds beside the outcome (the four
            # reasons sum to the refresh solves)
            assert f["n"] == d[f"phase.{cyl}.{kind}.count"]
    for cyl in cylinders:
        fro = kinds.get((cyl, "frozen"), {"n": 0, "accepted": 0})
        ref = kinds.get((cyl, "refresh"))
        if ref is None:
            assert fro["n"] == fro["accepted"]
            continue
        assert fro["n"] == fro["accepted"] + ref["declined"], cyl
    meg = kinds["hub", "mega"]
    assert meg["n"] == d["dispatch.mega_iterations"] > 0
    assert meg["budget"] == 1000 * meg["n"]
    assert meg["all_done"] <= meg["n"]
    assert meg["rejected_sweeps"] <= 1000 * d.get(
        "megastep.rejected_iterations", 0)
    # XhatShuffle evaluates cold; the Lagrangian re-solves warm
    assert kinds["spoke2", "refresh"]["cold"] >= 1
    # candidates: each priced or refused
    assert d["xhat.candidates"] >= 1
    refused = d.get("xhat.infeasible", 0)
    assert refused <= d["xhat.candidates"]
    assert 1 <= d["xhat.improved"] <= d["xhat.candidates"] - refused
    assert 1 <= d["hub.inner_bound_updates"] <= d["xhat.improved"]
