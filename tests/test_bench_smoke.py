"""Incremental-artifact contract of bench.py (BENCH_SMOKE stub mode).

The round-5 flagship failure mode: the driver's ``timeout`` SIGKILLed
bench.py mid-run (rc=124) and the artifact had parsed=null — every number
the run HAD produced was lost because the one JSON line printed only at
the very end.  bench.py now emits a valid partial parsed-JSON line after
*each* segment and the parent relays lines the moment they land, so a
kill at ANY point leaves rc-independent parseable content.

This test injects exactly that kill: it starts ``python bench.py`` in
smoke mode (tiny S, CPU, pinned cadence), SIGKILLs the whole process
group the moment the first segment line appears on stdout, and asserts
what was captured is a valid artifact carrying the new fields
(mfu_pct / vs_baseline_32rank / autotune cadence).
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


def _smoke_env():
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    env["BENCH_SMOKE"] = "1"
    return env


def test_bench_smoke_kill_leaves_parseable_artifact():
    proc = subprocess.Popen(
        [sys.executable, BENCH], env=_smoke_env(), cwd=REPO,
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        start_new_session=True,   # own process group: the kill takes the
    )                             # workload child down with the parent
    lines = []
    got_json = threading.Event()

    def _reader():
        for raw in proc.stdout:
            line = raw.decode(errors="replace").strip()
            lines.append(line)
            if line.startswith("{"):
                got_json.set()

    th = threading.Thread(target=_reader, daemon=True)
    th.start()
    try:
        # the injected mid-run kill: SIGKILL (un-catchable, exactly what
        # the driver's timeout -k sends) as soon as segment 1 lands
        assert got_json.wait(timeout=420), (
            "no JSON segment line within 420s; bench stdout so far: "
            + repr(lines[-5:]))
    finally:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait(timeout=30)
    th.join(timeout=10)

    parsed = None
    for line in lines:
        if line.startswith("{"):
            try:
                parsed = json.loads(line)   # EVERY emitted line must parse
            except json.JSONDecodeError as e:
                pytest.fail(f"unparseable artifact line {line!r}: {e}")
    assert parsed is not None
    # rc-independent contract: the process was SIGKILLed, yet the captured
    # content is a complete artifact for the segments that finished
    assert parsed.get("partial") is True
    assert parsed["metric"].startswith("ph_iters_per_sec_farmer")
    assert parsed["value"] > 0
    assert parsed["unit"] == "iter/s"
    assert parsed["vs_baseline"] > 0
    assert "vs_baseline_32rank" in parsed
    # the new accounting fields ride every segment line
    assert "mfu_pct" in parsed and "mfu_note" in parsed
    assert parsed["chunk"] >= 1 and parsed["refresh_every"] >= 1
    assert "autotuned" in parsed
    assert parsed["precision"] in ("default", "high", "highest")
    # host-sync accounting (overlapped dispatch pipeline, doc/pipeline.md)
    assert parsed["host_sync_count"] >= 1
    assert 0.0 <= parsed["dispatch_overhead_pct"] <= 100.0


def test_bench_ladder_emits_one_entry_per_rung():
    """--ladder: one parsed entry per rung, each carrying precision +
    mfu_pct, banked via the same partial-line protocol (rate-only smoke
    posture: BENCH_LADDER_RATE_ONLY skips the wheels)."""
    env = _smoke_env()
    env["BENCH_LADDER_SCENS"] = "2,3"
    proc = subprocess.run(
        [sys.executable, BENCH, "--workload", "--ladder"], env=env,
        cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        timeout=420,
    )
    parsed = None
    n_partial = 0
    for raw in proc.stdout.decode(errors="replace").splitlines():
        line = raw.strip()
        if not line.startswith("{"):
            continue
        obj = json.loads(line)      # every emitted line must parse
        n_partial += bool(obj.get("partial"))
        parsed = obj
    assert parsed is not None
    assert parsed["metric"] == "uc_certified_ladder"
    assert parsed["value"] == 2            # both rungs completed
    assert [r["S"] for r in parsed["rungs"]] == [2, 3]
    assert n_partial >= 2                  # each rung banked a partial line
    for rung in parsed["rungs"]:
        assert rung["precision"] in ("default", "high", "highest")
        assert "mfu_pct" in rung
        assert rung["ph_iters_per_sec"] > 0
        # rate-only smoke: the wheel fields exist, flagged skipped
        assert rung["wheel_skipped"] is True and "gap_pct" in rung
