"""One run of one cell: find the cell's files by name, drive it, reduce.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found here by the name that
``BENCHMARK.json`` gives it:

  configs/<config>.json          the deployment, as it is run
  workloads/<cell>.json          driver kind, its parameters, the checks
  drivers/<kind>.py              ``run(ctx) -> dict``: one general driver
                                 per kind of traffic
  layer_metrics/<metric>.py      ``read(obs) -> float | None``
  references/<name>.py           ``Reference(module, names, creator_kwargs)``:
                                 the plain truth of a deployment, named by
                                 the configuration's ``"reference"``
  checks/<check>.py              ``value(ev, spec) -> float | None``: one
                                 number that decides ``correct``
"""

from __future__ import annotations

import importlib
import json
import os
import sys
import time

from . import byname
from . import checks as _checks

BENCH_DIR = byname.BENCH_DIR
ROOT = os.path.dirname(BENCH_DIR)


class NoChip(RuntimeError):
    """jax found no TPU, or fewer chips than the cell asks for."""


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name, root=ROOT, bench_dir=BENCH_DIR):
    """The cell's entry of BENCHMARK.json with its configuration, its
    workload file and the metrics it reports."""
    bench = _json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = dict(cells[name])
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cell["config_file"] = _json(root, conf["file"])
    cell["workload_file"] = _json(bench_dir, "workloads", name + ".json")
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    cell["end_to_end"] = e2e
    cell["per_layer"] = [
        m for m in bench["per_layer"]
        if (name in m["workloads"] if "workloads" in m
            else m["moves"] in e2e_names)]
    return cell


def load_reader(metric, bench_dir=BENCH_DIR):
    """``layer_metrics/<metric>.py``, or, for a quantity that is split by
    the end-to-end metric it moves (``idle_pct.wheel``, ``idle_pct.serve``),
    the one reader named for what stands before the first dot."""
    return byname.load("layer_metrics", metric, bench_dir, "read", stem=True)


DEFAULT_REFERENCE = "two_stage_lp"


def load_reference(conf, bench_dir=BENCH_DIR):
    """The class ``Reference`` of ``references/<name>.py``, where ``name`` is
    the configuration's ``"reference"``; a configuration that names none
    gets the continuous two-stage one."""
    return byname.load("references",
                       conf.get("reference", DEFAULT_REFERENCE), bench_dir,
                       "Reference")


def device_info(chips, bench_dir=BENCH_DIR):
    """The device as jax reports it.  No TPU, fewer chips than the cell
    asks for, or a kind that ``peaks.json`` does not know, is an error."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu" or len(devs) < chips:
        raise NoChip(f"the cell needs {chips} tpu chip(s); "
                     f"jax reports {info}")
    peaks = _json(bench_dir, "peaks.json")["peaks"]
    if info["kind"] not in peaks:
        raise KeyError(f"device kind {info['kind']!r} is not in peaks.json "
                       f"(have {sorted(peaks)}): add it with its source")
    return info


def memory_peak_bytes():
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def data_seed(seed):
    """The models seed numpy's RandomState with ``offset + seedoffset``
    (offsets under 1e5), which takes whole numbers under 2**32."""
    return int(seed) % 4_000_000_000


class WindowRule:
    """The window opens when set-up ends and closes at the first boundary
    at or after ``seconds``: an iteration boundary the hub offers, or the
    completion of the request then in flight.  ``count`` is whatever the
    window counts (hub iterations, requests)."""

    def __init__(self, seconds):
        self.seconds = float(seconds)
        self.t0 = self.c0 = self.t1 = self.c1 = None

    @property
    def is_open(self):
        return self.t0 is not None and self.t1 is None

    def open(self, t, count):
        self.t0, self.c0 = float(t), count

    def offer(self, t, count):
        """A boundary at ``t``.  True closes the window there."""
        if self.is_open and t - self.t0 >= self.seconds:
            self.t1, self.c1 = float(t), count
            return True
        return False

    def close(self, t, count):
        """Close at a boundary that ends the run early (a certified gap)."""
        if self.is_open:
            self.t1, self.c1 = float(t), count

    @property
    def length(self):
        return self.t1 - self.t0

    @property
    def counted(self):
        return self.c1 - self.c0


def run_cell(name, seed, seconds, trace, *, t_start, root=ROOT,
             bench_dir=BENCH_DIR):
    """Drive one cell, as its files state it, and return the result line
    as a dict.  Needs the chip: :func:`device_info` refuses otherwise."""
    cell = load_cell(name, root, bench_dir)
    conf, wl = cell["config_file"], cell["workload_file"]
    device = device_info(cell["chips"], bench_dir)

    import jax

    from tpusppy.solvers import aot

    aot.arm_compile_cache()
    # keep every program, however quick its compile, so that a cell's
    # second run in a checkout compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    driver = importlib.import_module("benchmarks.drivers." + wl["driver"])
    ctx = {
        "cell": name, "config": conf, "workload": wl, "seed": int(seed),
        "data_seed": data_seed(seed), "seconds": float(seconds),
        "trace": bool(trace), "t_start": t_start, "bench_dir": bench_dir,
    }
    obs = driver.run(ctx)
    obs["workload"] = wl
    device["memory_peak_bytes"] = obs["memory_peak_bytes"]
    # a device number comes from a chip's trace or not at all
    obs["trace"] = (obs["tracer"].result()
                    if trace and device["platform"] == "tpu" else None)

    metrics = {}
    if trace:
        red = obs.get("trace")
        if red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
        for m in cell["per_layer"]:
            value = load_reader(m["name"], bench_dir)(obs)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": float(obs["end_to_end"][m["name"]]),
                                  "unit": m["unit"]}

    # the reference runs last: the window has closed, the peak is read
    correct, rows = _checks.decide(obs["evidence"], wl["checks"], bench_dir)
    line = {"correct": bool(correct and obs["failed"] == 0),
            "attempted": int(obs["attempted"]), "failed": int(obs["failed"]),
            "metrics": metrics, "device": device}
    if trace and obs.get("trace") is not None:
        line["breakdown"] = {"device_ops": obs["trace"]["device_ops"],
                             "idle_gaps": obs["trace"]["idle_gaps"]}
    line["workload"] = name
    line["seed"] = int(seed)
    line["window_s"] = obs["window_s"]
    line["notes"] = obs.get("notes", {})
    if trace and obs.get("trace") is not None:
        line["notes"]["trace_iterations"] = obs["trace"]["iterations"]
    line["checks"] = {r["name"]: {"value": r["value"], "limit": r["limit"],
                                  "ok": r["ok"]} for r in rows}
    return line


def print_checks(line, file=sys.stderr):
    print(f"-- {line['workload']} seed {line['seed']}: numbers compared, "
          "each beside its limit", file=file)
    for name, r in line["checks"].items():
        val = "n/a" if r["value"] is None else f"{r['value']:.6g}"
        print(f"{name} {val} limit {r['limit']:g} "
              f"{'ok' if r['ok'] else 'FAIL'}", file=file)
    print(f"correct {line['correct']} attempted {line['attempted']} "
          f"failed {line['failed']}", file=file, flush=True)


def finite_or_none(v):
    import math

    v = float(v)
    return v if math.isfinite(v) else None


def now():
    return time.monotonic()
