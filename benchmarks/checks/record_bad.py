def value(ev, spec):
    """Served cells: the record says done, and the iterations asked for
    were run unless the gap certified first."""
    rec = ev["record"]
    ok = rec["status"] == "done" and (
        rec["iters"] == ev["iter_limit"] or rec["certified"])
    return 0.0 if ok else 1.0
