"""Scheduler: seconds a request waited in the run queue before its first
slice (server record ``queue_wait_s``), mean over the window's requests."""


def read(obs):
    vals = [r["queue_wait_s"] for r in obs["records"]
            if r.get("queue_wait_s") is not None]
    return sum(vals) / len(vals) if vals else None
