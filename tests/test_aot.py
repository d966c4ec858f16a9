"""AOT executable cache (tpusppy/solvers/aot.py).

Contract pins: disarmed = strict passthrough; armed = serialize on miss,
deserialize on hit with IDENTICAL results (donation semantics included);
every invalidation axis (jax/jaxlib version, settings, mesh width,
corrupted/truncated file, foreign payload) produces a clean
miss-and-recompile — never a crash and never a stale hit (the tune
schema-v2 drop-wholesale lesson); programs carrying by-pointer custom
calls (LAPACK factorizations on CPU) are never persisted; and the tune
cache's key builder shares the aot key prefix so the two caches cannot
drift.
"""

import dataclasses
import os
import pickle

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpusppy.obs import metrics
from tpusppy.solvers import aot
from tpusppy.solvers.admm import ADMMSettings


def _set_jax_cache(enabled, path=None):
    """Re-arm jax's persistent compilation cache (the flag is memoized
    at first use, hence the reset)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_enable_compilation_cache", enabled)
    if path is not None:
        jax.config.update("jax_compilation_cache_dir", path)
    cc.reset_cache()


@pytest.fixture
def cache_dir(tmp_path):
    """An armed AOT store with jax's persistent cache OFF: an executable
    that comes out of a warm jax cache is deliberately not serialized on
    XLA:CPU (test_executable_from_jax_cache_is_not_serialized), so the
    serialize-on-miss pins below need every compile to be a real one."""
    d = tmp_path / "aot"
    aot.set_cache_path(str(d))
    jax_dir = jax.config.jax_compilation_cache_dir
    _set_jax_cache(False)
    yield str(d)
    _set_jax_cache(True, jax_dir)
    aot.reset()


def _toy():
    @jax.jit
    def f(x, s):
        return jnp.tanh(x) * s + x @ x.T @ x * 1e-3

    return f


def _aotx_files(d):
    try:
        return sorted(f for f in os.listdir(d) if f.endswith(".aotx"))
    except OSError:
        return []


def test_disarmed_is_passthrough(tmp_path):
    aot.reset()     # no cache path armed
    g = aot.cached_program(_toy(), "toy")
    x = np.ones((6, 6))
    r = g(x, 2.0)
    assert np.all(np.isfinite(np.asarray(r)))
    assert metrics.value("aot.hits") == 0
    assert metrics.value("aot.misses") == 0
    assert _aotx_files(str(tmp_path)) == []


def test_miss_serialize_then_fresh_process_hit(cache_dir):
    g = aot.cached_program(_toy(), "toy", key_extra=("k",))
    x = np.arange(36.0).reshape(6, 6)
    r1 = np.asarray(g(x, 2.0))
    assert metrics.value("aot.misses") == 1
    assert len(_aotx_files(cache_dir)) == 1
    # fresh-process posture: drop the in-memory executables, keep disk
    aot._loaded.clear()
    g2 = aot.cached_program(_toy(), "toy", key_extra=("k",))
    r2 = np.asarray(g2(x, 2.0))
    assert metrics.value("aot.hits") == 1
    np.testing.assert_array_equal(r1, r2)
    # same-signature second call reuses the in-memory executable
    r3 = np.asarray(g2(x, 3.0))
    assert metrics.value("aot.hits") == 1
    assert metrics.value("aot.misses") == 1
    assert np.all(np.isfinite(r3))


def _roundtrip_on(x, cache_dir):
    """Miss, then a fresh-store hit, for one placed input; returns the
    loaded program's output."""
    g = aot.cached_program(_toy(), "toy", key_extra=("placed",))
    r1 = g(x, 2.0)
    assert metrics.value("aot.misses") == 1
    aot._loaded.clear()
    r2 = aot.cached_program(_toy(), "toy", key_extra=("placed",))(x, 2.0)
    assert metrics.value("aot.hits") == 1
    assert metrics.value("aot.load_errors") == 0
    assert not [f for f in os.listdir(cache_dir) if f.endswith(".bad")]
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
    return r2


def test_one_device_of_eight_loads_onto_that_device(cache_dir):
    """The entry records the device the program was compiled for: a
    one-device program on a many-device host must come back on THAT
    device, not on every device of the backend."""
    dev = jax.devices()[3]
    x = jax.device_put(jnp.arange(36.0).reshape(6, 6), dev)
    assert _roundtrip_on(x, cache_dir).devices() == {dev}


def test_four_device_mesh_program_roundtrips(cache_dir):
    """A program sharded over a 4-device mesh (here devices 4..7 in
    reverse, so assignment ORDER matters) reloads onto the same mesh."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[4:8][::-1]), ("scen",))
    sh = NamedSharding(mesh, P("scen"))
    x = jax.device_put(jnp.arange(64.0).reshape(8, 8), sh)
    r = _roundtrip_on(x, cache_dir)
    assert r.sharding.device_set == set(mesh.devices.flat)


def test_entry_for_absent_device_is_a_miss_not_a_quarantine(cache_dir):
    g = aot.cached_program(_toy(), "toy")
    x = np.ones((6, 6))
    g(x, 2.0)
    (name,) = _aotx_files(cache_dir)
    path = os.path.join(cache_dir, name)
    with open(path, "rb") as f:
        obj = pickle.load(f)
    obj["devices"] = [10 ** 6]
    with open(path, "wb") as f:
        pickle.dump(obj, f)
    aot._loaded.clear()
    assert np.all(np.isfinite(np.asarray(
        aot.cached_program(_toy(), "toy")(x, 2.0))))
    assert metrics.value("aot.hits") == 0
    assert metrics.value("aot.load_errors") == 0
    assert not os.path.exists(path + ".bad")


def test_executable_from_jax_cache_is_not_serialized(cache_dir, tmp_path,
                                                     monkeypatch):
    """XLA:CPU: an executable handed back by jax's persistent cache
    re-serializes into an artifact that loads and then fails at execute
    (scripts/aot_cache_origin_probe.py), so the store must skip it —
    detected per compiling thread, without touching jax's global flag."""
    # a jax cache of this test's own, placed the way a caller places it
    # (aot.arm_compile_cache follows the variable)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jaxcache"))
    _set_jax_cache(True, str(tmp_path / "jaxcache"))
    min_secs = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        x = np.arange(36.0).reshape(6, 6)
        r1 = np.asarray(aot.cached_program(_toy(), "toy")(x, 2.0))
        (name,) = _aotx_files(cache_dir)       # a real compile: stored
        assert metrics.value("aot.from_jax_cache") == 0
        os.remove(os.path.join(cache_dir, name))
        aot._loaded.clear()
        r2 = np.asarray(aot.cached_program(_toy(), "toy")(x, 2.0))
        assert metrics.value("aot.from_jax_cache") == 1
        assert _aotx_files(cache_dir) == []
        np.testing.assert_array_equal(r1, r2)
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          min_secs)


def test_version_bump_is_clean_miss(cache_dir, monkeypatch):
    g = aot.cached_program(_toy(), "toy")
    x = np.ones((4, 4))
    r1 = np.asarray(g(x, 1.5))
    assert metrics.value("aot.misses") == 1
    # a jax/jaxlib upgrade changes every key: the old entry is simply
    # never read again — recompile, no crash, no stale hit
    aot._loaded.clear()
    monkeypatch.setattr(aot, "_versions",
                        lambda: ("99.0", "99.0", "cpu"))
    g2 = aot.cached_program(_toy(), "toy")
    r2 = np.asarray(g2(x, 1.5))
    assert metrics.value("aot.misses") == 2
    assert metrics.value("aot.load_errors") == 0
    np.testing.assert_array_equal(r1, r2)
    assert len(_aotx_files(cache_dir)) == 2      # both versions banked


def test_settings_and_width_change_keys():
    st = ADMMSettings()
    st2 = dataclasses.replace(st, megastep=1, sweep_precision="default")
    sig = (("t",), ((4, 4), "float64", False))
    k0 = aot.program_key("k", sig, repr((st, 1)))
    assert k0 == aot.program_key("k", sig, repr((st, 1)))   # deterministic
    assert k0 != aot.program_key("k", sig, repr((st2, 1)))  # settings
    assert k0 != aot.program_key("k", sig, repr((st, 8)))   # mesh width
    assert k0 != aot.program_key(
        "k", (("t",), ((8, 4), "float64", False)), repr((st, 1)))  # shape


def test_mesh_device_count_changes_program_key(cache_dir):
    """The same jitted fn wrapped under different mesh fingerprints must
    resolve to different entries (a 1-device executable must never serve
    an 8-device mesh)."""
    from tpusppy.parallel import sharded

    m1 = sharded.make_mesh(1)
    m8 = sharded.make_mesh()
    assert aot.mesh_fingerprint(m1) != aot.mesh_fingerprint(m8)
    assert aot.mesh_fingerprint(None) is None


@pytest.mark.parametrize("corruption", ["truncate", "garbage", "foreign"])
def test_corrupted_entry_is_clean_miss(cache_dir, corruption):
    g = aot.cached_program(_toy(), "toy")
    x = np.ones((5, 5))
    r1 = np.asarray(g(x, 2.0))
    (fname,) = _aotx_files(cache_dir)
    path = os.path.join(cache_dir, fname)
    if corruption == "truncate":
        blob = open(path, "rb").read()
        with open(path, "wb") as f:
            f.write(blob[: len(blob) // 3])
    elif corruption == "garbage":
        with open(path, "wb") as f:
            f.write(b"\x00not a pickle at all")
    else:   # valid pickle, foreign toolchain stamp: must be refused
        with open(path, "wb") as f:
            pickle.dump({"v": aot._FORMAT_VERSION, "jax": "0.0",
                         "jaxlib": "0.0", "platform": "cpu",
                         "payload": b"xx"}, f)
    aot._loaded.clear()
    g2 = aot.cached_program(_toy(), "toy")
    r2 = np.asarray(g2(x, 2.0))              # miss-and-recompile, no crash
    np.testing.assert_array_equal(r1, r2)
    assert metrics.value("aot.hits") == 0
    assert metrics.value("aot.misses") == 2
    aot._loaded.clear()
    g3 = aot.cached_program(_toy(), "toy")
    np.testing.assert_array_equal(r1, np.asarray(g3(x, 2.0)))
    if corruption == "foreign":
        # a foreign toolchain stamp is a version skip, not an error: the
        # recompile re-banks a healthy entry and the third process hits
        assert metrics.value("aot.load_errors") == 0
        assert metrics.value("aot.hits") == 1
    else:
        # a genuinely unreadable artifact QUARANTINES its key (this
        # toolchain's loader refuses some artifacts deterministically —
        # rewriting them would churn forever): the key stays a clean
        # miss on the jax-cache tier, never a crash, never a stale hit
        assert metrics.value("aot.load_errors") == 1
        assert metrics.value("aot.hits") == 0
        assert metrics.value("aot.quarantined") >= 1
        assert os.path.exists(
            os.path.join(cache_dir, fname + ".bad"))


def test_unserializable_program_never_persisted(cache_dir):
    """LAPACK-backed programs (cholesky on CPU) compile and run but are
    NOT written to disk — their deserialization in a fresh process is
    unsound on this toolchain (by-pointer custom calls)."""

    @jax.jit
    def f(a, b):
        K = a @ a.T + 8.0 * jnp.eye(a.shape[0])
        L = jnp.linalg.cholesky(K)
        return jax.scipy.linalg.solve_triangular(L, b, lower=True)

    g = aot.cached_program(f, "chol")
    a = np.random.default_rng(0).normal(size=(8, 8))
    r = np.asarray(g(a, np.ones((8, 2))))
    assert np.all(np.isfinite(r))
    assert metrics.value("aot.unserializable") == 1
    assert _aotx_files(cache_dir) == []
    # the in-memory executable still serves repeat calls
    np.testing.assert_array_equal(r, np.asarray(g(a, np.ones((8, 2)))))
    assert metrics.value("aot.misses") == 1


def test_loaded_executable_preserves_donation(cache_dir):
    import functools

    @functools.partial(jax.jit, donate_argnums=(0,))
    def f(x, y):
        return x * 2.0 + y

    g = aot.cached_program(f, "donated")
    r1 = np.asarray(g(jnp.ones((4,)), jnp.zeros((4,))))
    aot._loaded.clear()
    g2 = aot.cached_program(f, "donated")
    x = jnp.ones((4,))
    r2 = np.asarray(g2(x, jnp.zeros((4,))))
    np.testing.assert_array_equal(r1, r2)
    assert metrics.value("aot.hits") == 1
    assert x.is_deleted()        # the deserialized executable donates too


def test_nested_trace_inlines(cache_dir):
    g = aot.cached_program(_toy(), "toy")

    @jax.jit
    def outer(x):
        return g(x, 3.0)

    r = np.asarray(outer(np.ones((4, 4))))
    assert np.all(np.isfinite(r))
    # nested call traced through the plain jit twin: no cache traffic
    assert metrics.value("aot.hits") == 0
    assert metrics.value("aot.misses") == 0


def test_static_kwargs_join_key_and_strip_from_call(cache_dir):
    import functools

    @functools.partial(jax.jit, static_argnames=("mode",))
    def f(x, mode="a"):
        return x + (1.0 if mode == "a" else 2.0)

    g = aot.cached_program(f, "static", static_names=("mode",))
    x = np.zeros((3,))
    assert float(np.asarray(g(x, mode="a"))[0]) == 1.0
    assert float(np.asarray(g(x, mode="b"))[0]) == 2.0
    assert metrics.value("aot.misses") == 2      # one entry per static
    # warm process serves both
    aot._loaded.clear()
    g2 = aot.cached_program(f, "static", static_names=("mode",))
    assert float(np.asarray(g2(x, mode="b"))[0]) == 2.0
    assert float(np.asarray(g2(x, mode="a"))[0]) == 1.0
    assert metrics.value("aot.hits") == 2


def test_prewarm_loads_directory(cache_dir):
    g = aot.cached_program(_toy(), "toy")
    x = np.ones((7, 7))
    r1 = np.asarray(g(x, 2.0))
    aot._loaded.clear()
    assert aot.prewarm() == 1
    assert metrics.value("aot.prewarmed") == 1
    # the prewarmed executable serves the call without touching disk
    g2 = aot.cached_program(_toy(), "toy")
    np.testing.assert_array_equal(r1, np.asarray(g2(x, 2.0)))
    assert metrics.value("aot.misses") == 1      # only the cold compile


def test_solver_frozen_roundtrip_cross_cache(cache_dir):
    """The REAL steady-state program (admm.solve_batch_frozen) through
    the cache: miss -> serialize -> fresh-store resolve with identical
    results (pri/dua/x bitwise).

    The resolve is normally a deserialize hit; in a process whose XLA
    state was polluted by many earlier compiles (full-suite runs) this
    jaxlib's CPU loader can refuse the entry ("Symbols not found") —
    that path must be a CLEAN recorded load_error + recompile, never a
    crash and never a wrong result.  The guaranteed fresh-process hit is
    pinned by scripts/cold_warm_smoke.py (nightly) and the deps canary.
    """
    from tpusppy.solvers import admm

    rng = np.random.default_rng(3)
    S, n, m = 3, 5, 4
    A = rng.normal(size=(S, m, n))
    args = (rng.normal(size=(S, n)), np.full((S, n), 0.1), A,
            -np.ones((S, m)), np.ones((S, m)),
            -5.0 * np.ones((S, n)), 5.0 * np.ones((S, n)))
    st = ADMMSettings(max_iter=60, restarts=1, scaling_iters=3)
    sol, fac = admm._solve_impl(*map(jnp.asarray, args), st, None,
                                want_factors=True)
    r1 = admm.solve_batch_frozen(*args, fac, settings=st, warm=sol.raw)
    assert metrics.value("aot.misses") >= 1
    assert len(_aotx_files(cache_dir)) >= 1
    aot._loaded.clear()
    r2 = admm.solve_batch_frozen(*args, fac, settings=st, warm=sol.raw)
    assert (metrics.value("aot.hits")
            + metrics.value("aot.load_errors")) >= 1
    np.testing.assert_array_equal(np.asarray(r1.x), np.asarray(r2.x))
    np.testing.assert_array_equal(np.asarray(r1.pri_res),
                                  np.asarray(r2.pri_res))


def test_family_parts_is_tune_key_prefix():
    """Drift guard (the shared-key-builder satellite): the tune cache's
    verdict key must START with aot.family_parts verbatim — a change to
    either builder that desynchronizes them fails here."""
    from tpusppy import tune

    class _Arr:
        c = np.zeros((4, 6))
        cl = np.zeros((4, 3))
        A = np.zeros((4, 3, 6))

    st = ADMMSettings()
    parts = aot.family_parts(_Arr, st, None, "scen")
    key = tune._tune_key(_Arr, st, None, "scen", 1.0, (8,), 64, 30.0,
                         0.5, None, 1.5)
    assert key[: len(parts)] == parts
    assert parts == (_Arr.c.shape, _Arr.cl.shape, 3, st, 1, "scen")


def test_tune_aot_persist_kind_roundtrips(tmp_path):
    """The "aot" verdict kind rides the tune store: banked keys survive
    export/import (what checkpoints carry) and the disk file."""
    from tpusppy import tune

    tune.reset_persist()
    tune.set_cache_path(str(tmp_path / "tune.json"))
    tune._persist_put("aot", "somekey", {"keys": ["ph_frozen.abc"]})
    st = tune.export_state()
    assert st["aot"]["somekey"]["keys"] == ["ph_frozen.abc"]
    tune.reset_persist()
    tune.import_state(st)
    assert tune._persist_get("aot", "somekey")["keys"] == ["ph_frozen.abc"]
    tune.reset_persist()


def test_checkpoint_carries_cache_pointer(cache_dir):
    """capture_ph embeds the armed cache dir; a spinner resume re-arms
    from it (WheelSpinner._prewarm_executables consumes the meta)."""
    from tpusppy.resilience import checkpoint as ckpt

    class _Opt:
        W = np.zeros((2, 3))
        xbars = np.zeros((2, 3))
        xsqbars = np.zeros((2, 3))
        rho = np.ones((2, 3))
        _iter = 5
        all_scenario_names = ["a", "b"]

    ck = ckpt.capture_ph(_Opt())
    assert ck.meta["aot_cache"] == os.path.abspath(cache_dir)
    # no cache armed -> no pointer
    aot.set_cache_path(None)
    ck2 = ckpt.capture_ph(_Opt())
    assert "aot_cache" not in ck2.meta
