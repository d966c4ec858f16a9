"""tpusppy.obs: zero-dependency tracing + metrics + run reporting.

One subsystem for every number and event the stack emits about itself:

- :mod:`.trace` — a thread-safe bounded ring buffer of structured events
  (spans / instants / counters), OFF by default at near-zero cost, enabled
  via ``TPUSPPY_TRACE=<path>`` or :func:`trace.enable`;
- :mod:`.metrics` — the process-wide registry of counters / gauges /
  histograms that the host-sync trackers (:mod:`tpusppy.solvers.hostsync`)
  and the dispatch/speculation billing feed, and that every number
  ``bench.py`` reports is sourced from;
- :mod:`.perfetto` — export of the trace ring as Chrome/Perfetto
  trace-event JSON (open at https://ui.perfetto.dev);
- :mod:`.report` — the post-run "flight recorder" summary: gap-vs-wall and
  bound-vs-wall arrays, per-track span totals, counter dump;
- :mod:`.log` — ``get_logger(name)`` with the ``[track] message`` format
  and the ``TPUSPPY_LOG_LEVEL`` knob (:mod:`tpusppy.log` re-exports it);
- :mod:`.telemetry` — the LIVE serving plane: request-scoped trace
  propagation (``trace_id`` context, per-request tracks, clock-sync
  stamps for ``scripts/trace_merge.py``), Prometheus text exposition +
  the zero-dependency scrape endpoint, and the bounded per-request
  progress bus ``SolveClient.watch`` streams from.

Grew out of the PR-3 fragments (hostsync fetch counters, per-segment
``mfu_pct`` / ``dispatch_overhead_pct``); see doc/observability.md for the
event catalogue and track naming.
"""

from . import log, metrics, perfetto, report, telemetry, trace  # noqa: F401

__all__ = ["log", "metrics", "perfetto", "report", "telemetry", "trace"]
