"""Scenario scale-out (doc/scaling.md): rule-driven placement, ghost
padding for uneven S, the lean (O(1)-host) megastep pack + device-resident
PH state, the bucketed wheel megakernel, shard-written checkpoints, and
the megastep tune-key drift guard.
"""

import dataclasses
import os

import numpy as np
import pytest

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from tpusppy.ir import BucketedBatch, ScenarioBatch
from tpusppy.models import farmer
from tpusppy.obs import metrics as obs_metrics
from tpusppy.parallel import sharded
from tpusppy.resilience import checkpoint as ckpt
from tpusppy.solvers.admm import ADMMSettings


def make_batch(n, **kw):
    names = farmer.scenario_names_creator(n)
    return ScenarioBatch.from_problems(
        [farmer.scenario_creator(nm, num_scens=n, **kw) for nm in names])


# ---------------------------------------------------------------------------
# Rule-driven placement (sharded.ph_partition_rules / match_partition_rules)
# ---------------------------------------------------------------------------
class TestPartitionRules:
    def test_every_ph_leaf_has_a_rule(self):
        """Every PHArrays AND PHState leaf matches exactly through the
        table — the placement contract shard_batch/init_state build on."""
        S, n, m, K, N = 4, 3, 2, 2, 3
        arr = sharded.PHArrays(
            c=np.zeros((S, n)), q2=np.zeros((S, n)),
            A=np.zeros((S, m, n)), cl=np.zeros((S, m)),
            cu=np.zeros((S, m)), lb=np.zeros((S, n)), ub=np.zeros((S, n)),
            const=np.zeros(S), probs=np.zeros(S),
            onehot=np.zeros((S, K, N)), nid_sk=np.zeros((S, K), int))
        rules = sharded.ph_partition_rules()
        specs = sharded.match_partition_rules(rules, arr)
        assert all(s == P("scen") for s in specs)
        st = sharded.PHState(*[np.zeros((S, 2))] * 7)
        sspecs = sharded.match_partition_rules(rules, st)
        assert all(s == P("scen") for s in sspecs)

    def test_shared_posture_rules(self):
        """Shared-A posture: A replicated (or row-sharded on a 2-D
        mesh), row-state (cl/cu/z/y) sharded on both axes there."""
        rules = sharded.ph_partition_rules(shared=True)
        d = {r: s for r, s in rules}
        assert d[r"(^|/)A(/|$)"] == P()
        rules2 = sharded.ph_partition_rules(row_axis="row", shared=True)
        d2 = {r: s for r, s in rules2}
        assert d2[r"(^|/)A(/|$)"] == P("row", None)
        assert d2[r"(^|/)(cl|cu|z|y)$"] == P("scen", "row")

    def test_unmatched_leaf_is_loud(self):
        """An unplaced leaf is a table bug, never a silently replicated
        (S, ...) array."""
        with pytest.raises(ValueError, match="no partition rule"):
            sharded.match_partition_rules(
                sharded.ph_partition_rules(),
                {"mystery_leaf": np.zeros((4, 2))})

    def test_scalars_never_partition(self):
        specs = sharded.match_partition_rules(
            sharded.ph_partition_rules(), {"A": np.zeros(())})
        assert specs["A"] == P()

    def test_sparse_A_subtree_matches_whole(self):
        """A SparseA constraint matrix matches the A rule leaf-wise (its
        sub-leaves carry the A path prefix) — replicated, like the dense
        shared matrix."""
        from tpusppy.solvers.sparse import SparseA

        sp = SparseA.from_dense(np.eye(8))
        specs = sharded.match_partition_rules(
            sharded.ph_partition_rules(shared=True), {"A": sp})
        leaves = jax.tree.leaves(
            specs, is_leaf=lambda x: isinstance(x, P))
        assert leaves and all(s == P() for s in leaves)

    def test_state_shardings_match_data(self):
        """init_state's rule-derived shardings equal the data shardings
        (the first step must not reshard)."""
        batch = make_batch(4)
        mesh = sharded.make_mesh(4)
        st = ADMMSettings()
        arr = sharded.shard_batch(batch, mesh)
        state = sharded.init_state(arr, 1.0, st)
        assert state.W.sharding == arr.nid_sk.sharding
        assert state.x.sharding == arr.c.sharding
        assert state.z.sharding == arr.cl.sharding


# ---------------------------------------------------------------------------
# Ghost-scenario padding: uneven S over the mesh (satellite 1)
# ---------------------------------------------------------------------------
class TestGhostPadding:
    def test_num_ghosts(self):
        mesh = sharded.make_mesh(4)
        assert sharded.num_ghosts(7, mesh) == 1
        assert sharded.num_ghosts(8, mesh) == 0

    def test_ghosts_are_masked(self):
        """Ghost rows: zero probability AND zero node membership — inert
        in every psum-lowered reduction."""
        batch = make_batch(7)
        mesh = sharded.make_mesh(4)
        arr = sharded.shard_batch(batch, mesh)
        assert arr.c.shape[0] == 8
        probs = np.asarray(arr.probs)
        onehot = np.asarray(arr.onehot)
        assert probs[7] == 0.0
        assert np.all(onehot[7] == 0.0)
        assert probs[:7].sum() == pytest.approx(1.0)

    def test_uneven_s_exact_on_4_device_mesh(self):
        """S=7 on a 4-device mesh: the ghost-padded run must agree with
        the unpadded single-device run — uneven S is exact, not
        approximately padded (the reductions see zero ghost weight)."""
        batch = make_batch(7)
        settings = ADMMSettings(max_iter=200, restarts=2)
        st4, out4 = sharded.run_ph(batch, sharded.make_mesh(4), iters=30,
                                   settings=settings)
        st1, out1 = sharded.run_ph(batch, sharded.make_mesh(1), iters=30,
                                   settings=settings)
        assert float(out4.eobj) == pytest.approx(float(out1.eobj),
                                                 rel=1e-3)
        np.testing.assert_allclose(np.asarray(st4.xbars)[:7],
                                   np.asarray(st1.xbars)[:7],
                                   rtol=0.02, atol=0.5)


# ---------------------------------------------------------------------------
# Lean megastep pack + device-resident PH state (O(1)-host wheel)
# ---------------------------------------------------------------------------
class TestLeanMegastep:
    def test_measure_len(self):
        S, n, K = 10, 6, 3
        full = sharded.megastep_measure_len(4, S, n, K)
        lean = sharded.megastep_measure_len(4, S, n, K, pack="lean")
        assert full - lean == S * n + 2 * S * K
        assert lean == sharded.MEGA_STATS * 4 + 2 + 3 * S

    def test_lean_pack_device_parity(self):
        """The lean program returns the SAME device state as the full
        one; its packed vector is exactly the full vector's prefix."""
        settings = ADMMSettings(max_iter=120, restarts=2)
        batch = make_batch(5)
        mesh = sharded.make_mesh(1)
        arr = sharded.shard_batch(batch, mesh)
        idx = batch.tree.nonant_indices
        refresh, _ = sharded.make_ph_step_pair(idx, settings, mesh)
        state = sharded.init_state(arr, 1.0, settings)
        state, _, _ = refresh(state, arr, 0.0)
        state, _, factors = refresh(state, arr, 1.0)
        full = sharded.make_wheel_megastep(idx, settings, mesh, n_iters=3,
                                           donate=False)
        lean = sharded.make_wheel_megastep(idx, settings, mesh, n_iters=3,
                                           donate=False, pack="lean")
        s_f, p_f = full(state, arr, 1.0, factors, -1.0, 3, np.inf)
        s_l, p_l = lean(state, arr, 1.0, factors, -1.0, 3, np.inf)
        np.testing.assert_array_equal(np.asarray(s_l.W), np.asarray(s_f.W))
        np.testing.assert_array_equal(np.asarray(s_l.x), np.asarray(s_f.x))
        np.testing.assert_array_equal(
            np.asarray(p_l), np.asarray(p_f)[:p_l.shape[0]])
        S, n = arr.c.shape
        K = arr.nid_sk.shape[1]
        m = sharded.megastep_unpack(np.asarray(p_l), 3, S, n, K,
                                    pack="lean")
        assert "W" not in m and "x" not in m
        assert m["executed"] == 3
        mf = sharded.megastep_unpack(np.asarray(p_f), 3, S, n, K)
        np.testing.assert_array_equal(m["pri"], mf["pri"])

    def test_device_state_wheel_matches_legacy(self):
        """ph_device_state: lean windows + boundary syncs produce the
        SAME host-visible final state as the legacy full-pack wheel, with
        the boundary fetches counted (phstate.boundary_fetches)."""
        from tpusppy.opt.ph import PH

        n = 4
        names = farmer.scenario_names_creator(n)

        def run(dev):
            opts = {"defaultPHrho": 1.0, "PHIterLimit": 12,
                    "convthresh": -1.0, "solver_refresh_every": 6,
                    "ph_device_state": dev}
            ph = PH(opts, names, farmer.scenario_creator,
                    scenario_creator_kwargs={"num_scens": n})
            with obs_metrics.window() as w:
                ph.ph_main(finalize=False)
                # deltas are LIVE views — bank them inside the window
                d = {k: int(w.delta(k)) for k in (
                    "dispatch.megasteps", "phstate.boundary_fetches")}
            return ph, d

        ph0, d0 = run(False)
        ph1, d1 = run(True)
        assert d1["dispatch.megasteps"] >= 1
        assert d1["phstate.boundary_fetches"] >= 1
        assert d0["phstate.boundary_fetches"] == 0
        np.testing.assert_allclose(ph1.W, ph0.W, atol=1e-9)
        np.testing.assert_allclose(ph1.xbars, ph0.xbars, atol=1e-9)
        np.testing.assert_allclose(ph1.local_x, ph0.local_x, atol=1e-9)
        assert ph1.conv == pytest.approx(ph0.conv, abs=1e-12)

    def test_device_state_checkpoint_capture_fresh(self, tmp_path):
        """A due checkpoint finds FRESH host mirrors (the pre-sync runs
        before spcomm.sync) and the capture itself stays zero-fetch."""
        from tpusppy.cylinders import PHHub
        from tpusppy.opt.ph import PH
        from tpusppy.spin_the_wheel import WheelSpinner

        n = 4
        names = farmer.scenario_names_creator(n)
        hub = {"hub_class": PHHub,
               "hub_kwargs": {"options": {
                   "checkpoint_dir": str(tmp_path / "ck"),
                   "checkpoint_every_iters": 3,
                   "checkpoint_every_secs": None}},
               "opt_class": PH,
               "opt_kwargs": {
                   "options": {"defaultPHrho": 1.0, "PHIterLimit": 10,
                               "convthresh": -1.0,
                               "solver_refresh_every": 6,
                               "ph_device_state": True},
                   "all_scenario_names": names,
                   "scenario_creator": farmer.scenario_creator,
                   "scenario_creator_kwargs": {"num_scens": n}}}
        with obs_metrics.window() as w:
            ws = WheelSpinner(hub, []).spin()
        assert int(w.delta("checkpoint.captures")) >= 2
        assert int(w.delta("checkpoint.capture_fetches")) == 0
        opt = ws.spcomm.opt
        ck = ckpt.load_latest(str(tmp_path / "ck"))
        assert ck is not None and ck.W is not None
        # the final capture saw the SYNCED mirrors (loop-exit sync)
        if ck.iteration == opt._iter:
            np.testing.assert_array_equal(ck.W, opt.W)


# ---------------------------------------------------------------------------
# Bucketed wheel megastep (ragged families, tentpole b)
# ---------------------------------------------------------------------------
class TestBucketedMegastep:
    @staticmethod
    def make_ph(iters, mega, **extra):
        from tpusppy.opt.ph import PH

        opts = {"defaultPHrho": 1.0, "PHIterLimit": iters,
                "convthresh": -1.0, "bundles_per_rank": 3,
                "shape_buckets": True, "shape_bucket_quantum": 1,
                "solver_refresh_every": 6,
                "solver_options": {"megastep": mega}, **extra}
        return PH(opts, farmer.scenario_names_creator(7),
                  farmer.scenario_creator,
                  scenario_creator_kwargs={"num_scens": 7})

    def test_bucketed_megastep_engages_and_matches_legacy(self):
        """Mixed-shape farmer bundles (two buckets — 3-merge and 2-merge
        shapes): the bucketed megakernel engages and the trajectory
        matches the legacy scattered host path (host-vs-device objective
        assembly differs in ulps; 1e-9, the homogeneous gate)."""
        ph1 = self.make_ph(12, 0)
        with obs_metrics.window() as w:
            ph1.ph_main(finalize=False)
        assert isinstance(ph1.batch, BucketedBatch)
        assert len(ph1.batch.buckets) == 2
        assert int(w.delta("dispatch.megasteps")) >= 1
        assert int(w.delta("dispatch.mega_iterations")) >= 2
        ph0 = self.make_ph(12, 1)
        with obs_metrics.window() as w0:
            ph0.ph_main(finalize=False)
        assert int(w0.delta("dispatch.megasteps")) == 0
        np.testing.assert_allclose(ph1.W, ph0.W, atol=1e-9)
        np.testing.assert_allclose(ph1.xbars, ph0.xbars, atol=1e-9)
        np.testing.assert_allclose(ph1.local_x, ph0.local_x, atol=1e-9)
        assert ph1.conv == pytest.approx(ph0.conv, abs=1e-11)

    def test_bucketed_window_bitwise_vs_serial_windows(self):
        """Device-level parity: one N-iteration bucketed megastep equals
        N single-iteration bucketed megasteps BITWISE (same jitted
        sub-programs, one dispatch vs N) — the scattered host path lifted
        per-bucket."""
        # two identically-constructed PH objects — deterministic setup
        # gives them bitwise-identical slots/state after the same legacy
        # warmup iteration
        phA = self.make_ph(1, 0)
        phB = self.make_ph(1, 0)
        for ph in (phA, phB):
            ph.ph_main(finalize=False)
        mA = phA._megastep_solve_bucketed(3, 3, -1.0, phA.W, phA.xbars,
                                          phA.rho)
        assert mA["executed"] == 3
        outB = []
        for _ in range(3):
            mB = phB._megastep_solve_bucketed(1, 1, -1.0, phB.W,
                                              phB.xbars, phB.rho)
            assert mB["executed"] == 1
            phB._apply_megastep_meas(phB._iter + 1, mB)
            outB.append(mB)
        np.testing.assert_array_equal(mA["W"], outB[-1]["W"])
        np.testing.assert_array_equal(mA["xbars"], outB[-1]["xbars"])
        np.testing.assert_array_equal(mA["x"], outB[-1]["x"])
        np.testing.assert_array_equal(mA["pri"], outB[-1]["pri"])
        np.testing.assert_array_equal(
            mA["conv"], np.array([m["conv"][0] for m in outB]))

    @pytest.mark.slow   # uc_lite two-bucket family traces ~4 programs (>5s)
    def test_bucketed_shared_engine_parity(self):
        """A uc_lite family bucketed by INTEGER PATTERN (3 relaxed + 2
        integer scenarios — same shapes, different ``is_int``): both
        buckets keep their genuine identity-shared A, so the bucketed
        megakernel runs the SHARED-A engine per bucket (and the lifted
        host path dispatches it too), trajectory matching the
        forced-legacy scattered path."""
        from tpusppy.models import uc_lite
        from tpusppy.opt.ph import PH
        from tpusppy.spopt import bucket_shared

        S = 5

        def creator(nm, num_scens=None):
            from tpusppy.utils.sputils import extract_num

            return uc_lite.scenario_creator(
                nm, num_scens=num_scens,
                relax_integers=extract_num(nm) < 3)

        def run(mega):
            opts = {"defaultPHrho": 1.0, "PHIterLimit": 10,
                    "convthresh": -1.0,
                    "shape_buckets": True, "shape_bucket_quantum": 1,
                    "solver_refresh_every": 6,
                    "solver_options": {"megastep": mega}}
            ph = PH(opts, uc_lite.scenario_names_creator(S), creator,
                    scenario_creator_kwargs={"num_scens": S})
            with obs_metrics.window() as w:
                ph.ph_main(finalize=False)
                megasteps = int(w.delta("dispatch.megasteps"))
            return ph, megasteps

        ph1, megasteps = run(0)
        assert isinstance(ph1.batch, BucketedBatch)
        assert len(ph1.batch.buckets) == 2
        assert all(bucket_shared(sub) for _, sub in ph1.batch.buckets)
        assert megasteps >= 1
        ph0, _ = run(1)
        np.testing.assert_allclose(ph1.W, ph0.W, atol=1e-9)
        np.testing.assert_allclose(ph1.local_x, ph0.local_x, atol=1e-9)

    def test_bucketed_cap_multi_sums_buckets(self):
        from tpusppy.solvers import segmented

        st = ADMMSettings(max_iter=200)
        one = segmented.megastep_cap(100, 50, 60, st)
        two = segmented.megastep_cap_multi(
            [(100, 50, 60), (100, 50, 60)], st)
        assert two <= one
        assert two >= segmented.megastep_cap(200, 50, 60, st) // 2


# ---------------------------------------------------------------------------
# Shard-written checkpoints (tentpole d)
# ---------------------------------------------------------------------------
class TestShardedCheckpoints:
    def _write_set(self, d, S=7, K=3, it=12, nshards=3):
        W = np.arange(S * K, dtype=float).reshape(S, K)
        rho = np.full((S, K), 2.5)
        cuts = np.linspace(0, S, nshards + 1).astype(int)
        for k in range(nshards):
            lo, hi = cuts[k], cuts[k + 1]
            c = ckpt.WheelCheckpoint(iteration=it, W=W[lo:hi],
                                     rho=rho[lo:hi], best_inner=5.0,
                                     best_outer=1.0)
            ckpt.save_shard(c, d, k, nshards, (lo, hi), S)
        return W, rho

    def test_round_trip_assembled(self, tmp_path):
        d = str(tmp_path)
        W, rho = self._write_set(d)
        cks = ckpt.list_checkpoints(d)
        assert len(cks) == 1 and cks[0][0] == 12
        full = ckpt.load_latest(d)
        np.testing.assert_array_equal(full.W, W)
        np.testing.assert_array_equal(full.rho, rho)
        assert full.iteration == 12 and full.best_inner == 5.0
        assert "shard" not in (full.meta or {})

    def test_incomplete_set_invisible(self, tmp_path):
        """A torn set (kill between shard renames) must never become
        ``latest`` — the previous complete checkpoint survives."""
        d = str(tmp_path)
        self._write_set(d, it=12)
        c = ckpt.WheelCheckpoint(iteration=20, W=np.zeros((3, 3)))
        ckpt.save_shard(c, d, 0, 3, (0, 3), 7)   # only shard 0 of 3
        assert ckpt.latest(d).endswith(".s000of003.npz")
        assert ckpt.load_latest(d).iteration == 12

    def test_device_restore_reads_rows_only(self, tmp_path):
        """make_array_from_callback restore over the 8-device mesh with
        ghost-padded rows, under the D2H transfer guard (the restore is
        H2D only)."""
        d = str(tmp_path)
        W, _ = self._write_set(d, S=7)
        mesh = sharded.make_mesh(4)
        shd = NamedSharding(mesh, P("scen"))
        with jax.transfer_guard_device_to_host("disallow"):
            Wd = ckpt.restore_sharded_array(ckpt.latest(d), "W", shd,
                                            (8, 3))
        got = np.asarray(Wd)
        np.testing.assert_array_equal(got[:7], W)
        assert np.all(got[7:] == 0.0)

    def test_reader_row_ranges(self, tmp_path):
        d = str(tmp_path)
        W, _ = self._write_set(d, S=7, nshards=3)
        r = ckpt.ShardedCheckpointReader(ckpt.latest(d))
        np.testing.assert_array_equal(r.read_rows("W", 1, 6), W[1:6])
        # all-ghost request (a device owning only padding rows)
        assert np.all(r.read_rows("W", 7, 9) == 0.0)
        assert r.iteration == 12

    def test_plain_manager_prunes_whole_shard_set(self, tmp_path):
        """A NON-sharded manager reusing a directory with sharded sets
        must remove whole sets (list_checkpoints names a set by its
        shard-0 path — removing that alone would orphan the siblings)."""
        d = str(tmp_path)
        self._write_set(d, it=5, nshards=3)
        self._write_set(d, it=9, nshards=3)
        mgr = ckpt.CheckpointManager(d, every_secs=None, every_iters=1,
                                     keep=1)
        mgr.capture(10, lambda: ckpt.WheelCheckpoint(
            iteration=10, W=np.zeros((7, 3))))
        assert mgr.flush()
        mgr.close()
        names = sorted(os.listdir(d))
        # keep=1: only the new single-file checkpoint survives; no
        # orphaned .sNNNofNNN siblings linger
        assert names == ["ckpt_wheel_00000010.npz"]

    def test_manager_shard_mode_prunes_own_files(self, tmp_path):
        d = str(tmp_path)
        mgr = ckpt.CheckpointManager(d, every_secs=None, every_iters=1,
                                     keep=2, shard=(0, 2, (0, 4), 8))
        for it in (1, 2, 3):
            mgr.capture(it, lambda it=it: ckpt.WheelCheckpoint(
                iteration=it, W=np.zeros((4, 2))))
        assert mgr.flush()
        mgr.close()
        names = sorted(os.listdir(d))
        own = [n for n in names if n.endswith(".s000of002.npz")]
        assert len(own) == 2       # keep=2 pruned iteration 1
        assert all("of002" in n for n in own)


# ---------------------------------------------------------------------------
# Megastep tune-key drift guard (satellite 6)
# ---------------------------------------------------------------------------
class TestMegastepKeyDriftGuard:
    def test_shape_family_parts_matches_family_parts(self):
        """The bare-shape key builder and the array key builder produce
        the SAME tuple structure — tune megastep keys can never silently
        drift from aot.family_parts."""
        from tpusppy.solvers import aot

        batch = make_batch(3)
        mesh = sharded.make_mesh(1)
        arr = sharded.shard_batch(batch, mesh)
        st = ADMMSettings()
        via_arr = aot.family_parts(arr, st, None, "scen")
        via_shape = aot.shape_family_parts(
            arr.c.shape[0], arr.c.shape[1], arr.cl.shape[1], st,
            a_kind=arr.A.ndim)
        assert via_arr == via_shape

    def test_s1000_verdict_never_serves_s10000(self, tmp_path):
        """The ladder shares one TPUSPPY_TUNE_CACHE across rungs: a
        megastep verdict banked at S=1000 must never serve S=10000 (S
        rides the key), in memory AND through the persistent store."""
        from tpusppy import tune

        st = ADMMSettings()
        tune.set_cache_path(str(tmp_path / "tune.json"))
        try:
            res = tune.autotune_megastep(
                lambda n: n, (1000, 44, 30), n_cap=32, settings=st)
            assert res.n >= 1
            assert tune.megastep_verdict(1000, 44, 30,
                                         settings=st) == res.n
            assert tune.megastep_verdict(10000, 44, 30,
                                         settings=st) is None
            # settings ride the key too: a different sweep budget is a
            # different family
            st2 = dataclasses.replace(st, max_iter=st.max_iter + 1)
            assert tune.megastep_verdict(1000, 44, 30,
                                         settings=st2) is None
            # bucketed keys carry EVERY bucket's shape
            resb = tune.autotune_megastep(
                lambda n: n, ((500, 10, 8), (500, 12, 8)), n_cap=8,
                settings=st)
            assert tune.megastep_verdict(
                ((500, 10, 8), (500, 12, 8)), settings=st) == resb.n
            assert tune.megastep_verdict(
                ((5000, 10, 8), (5000, 12, 8)), settings=st) is None
        finally:
            tune.set_cache_path(None)
