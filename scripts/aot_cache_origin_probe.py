#!/usr/bin/env python
"""Does an executable that came out of jax's persistent compilation cache
survive ``serialize`` -> fresh-process ``deserialize_and_load`` -> execute?

Establishes, per backend, the fault behind ``aot.from_jax_cache``
(tpusppy/solvers/aot.py): three REAL processes on whatever backend jax
picks (this parent never imports jax, so on a chip each child gets it)::

    python scripts/aot_cache_origin_probe.py

1. ``populate``  — fresh jax cache dir: compile, run (writes the entry).
2. ``fromcache`` — same dir: the compile is a persistent-cache HIT; that
   executable is serialized to ``art.pkl``.
3. ``load``      — deserializes ``art.pkl`` onto device 0 and executes.

Last line is one JSON object ``{"platform", "from_cache_roundtrip_ok",
"detail"}``.  On XLA:CPU (jaxlib 0.9.0) step 3 fails at execute with
``Function wrapped_add not found``.
"""

import json
import os
import pickle
import subprocess
import sys
import tempfile


def _child(mode: str, work: str):
    import jax
    import jax.numpy as jnp
    from jax.experimental import serialize_executable as se

    jax.config.update("jax_compilation_cache_dir", os.path.join(work, "jc"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    hits = []
    jax.monitoring.register_event_listener(
        lambda ev, **kw: hits.append(ev)
        if ev == "/jax/compilation_cache/cache_hits" else None)

    def g(x):
        return jax.lax.fori_loop(
            0, 5, lambda i, c: c @ c * 0.5 + jnp.sin(c), x)

    x = jnp.eye(16, dtype=jnp.float32)
    art = os.path.join(work, "art.pkl")
    out = {"mode": mode, "platform": jax.devices()[0].platform}
    if mode == "load":
        with open(art, "rb") as f:
            loaded = se.deserialize_and_load(
                *pickle.load(f), execution_devices=[jax.devices()[0]])
        out["sum"] = float(loaded(x).sum())
    else:
        compiled = jax.jit(g).lower(x).compile()
        out["sum"] = float(compiled(x).sum())
        out["cache_hit"] = bool(hits)
        if mode == "fromcache":
            with open(art, "wb") as f:
                pickle.dump(se.serialize(compiled), f)
    print(json.dumps(out), flush=True)


def main() -> int:
    if len(sys.argv) == 3:
        _child(sys.argv[1], sys.argv[2])
        return 0
    outs = {}
    with tempfile.TemporaryDirectory() as work:
        for mode in ("populate", "fromcache", "load"):
            r = subprocess.run(
                [sys.executable, os.path.abspath(__file__), mode, work],
                capture_output=True, text=True, timeout=600)
            line = r.stdout.strip().splitlines()[-1:] or [""]
            try:
                outs[mode] = json.loads(line[0])
            except ValueError:
                outs[mode] = {"rc": r.returncode,
                              "err": r.stderr.strip().splitlines()[-1:]}
    ok = ("sum" in outs["load"] and outs["fromcache"].get("cache_hit")
          and outs["load"]["sum"] == outs["populate"].get("sum"))
    print(json.dumps({
        "platform": outs["populate"].get("platform"),
        "from_cache_roundtrip_ok": bool(ok), "detail": outs}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
