"""Ingest, AOT bind and Iter0: the server record's own ``ttfi_s`` (slice
start to the end of the hub's Iter0), mean over the window's requests."""


def read(obs):
    vals = [r["ttfi_s"] for r in obs["records"] if r.get("ttfi_s") is not None]
    return sum(vals) / len(vals) if vals else None
