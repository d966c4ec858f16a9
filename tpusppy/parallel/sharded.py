"""Scenario-sharded PH over a `jax.sharding.Mesh` — the multi-chip path.

This is the TPU-native replacement for the reference's rank-level scenario
parallelism (P1/P2 in SURVEY §2.12): scenarios are block-partitioned over MPI
ranks there (``spbase.py:184-216``, ``sputils.py:774-840``) with per-tree-node
``Allreduce`` reductions (``phbase.py:27-107``, ``spbase.py:333-375``).  Here
the whole scenario batch is sharded over a named mesh axis (``"scen"``); each
device solves its local shard of subproblems inside ONE jitted program, and the
per-node weighted averages are a one-hot contraction whose scenario-axis
reduction XLA lowers to a psum over ICI.  No explicit communicator management:
the mesh + sharding annotations replace ``comm.Split``.

The functional core (:func:`make_ph_step`) is also the single-chip fast path:
the same compiled step runs on one device with a trivial mesh.
"""

from __future__ import annotations

import dataclasses
import functools
import re
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..solvers import admm, shared_admm
from ..solvers import aot as aot_cache
from ..solvers import segmented as segmented_solvers
from ..solvers.admm import ADMMSettings
from ..solvers.sparse import SparseA


def _shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)

# ---------------------------------------------------------------------------
# Dispatch segmentation: every program execution is held to a wall-clock
# budget, so reference-scale UC (S=1000, n=16008) does not run as one
# monolithic PH step — see tpusppy/solvers/segmented.py for the shared
# mechanism.  The constants live here too so tests can monkeypatch
# this module's copies; _dispatch_segments forwards them explicitly.
# ---------------------------------------------------------------------------
# None = defer to segmented's defaults (including its per-scenario-dense
# throughput clamp); tests monkeypatch these with explicit numbers to force
# dispatch regimes — explicit values are authoritative, never clamped
_DISPATCH_TARGET_SECS = None
_DISPATCH_EFF_FLOPS = None


def _dispatch_segments(S, n, m, st: ADMMSettings, factor_batch=1,
                       sparse_factor=1.0):
    return segmented_solvers.dispatch_segments(
        S, n, m, st, factor_batch=factor_batch,
        eff_flops=_DISPATCH_EFF_FLOPS, target_secs=_DISPATCH_TARGET_SECS,
        sparse_factor=sparse_factor)


def _dispatch_model_params(arr, mesh):
    """(S_dev, n, m, factor_batch, sparse_factor) for the dispatch flop
    model — single source for _segments_for and fused_iteration_cap."""
    S, n = arr.c.shape
    m = arr.cl.shape[1]
    ndev = 1 if mesh is None else len(mesh.devices.flat)
    S_dev = -(-S // ndev)          # per-device shard does the sweeping
    dense = arr.A.ndim == 3
    sf = (segmented_solvers.SPARSE_DISPATCH_FACTOR
          if isinstance(arr.A, SparseA) else 1.0)
    return S_dev, n, m, (S_dev if dense else 1), sf


class PHArrays(NamedTuple):
    """Device-resident, scenario-sharded problem data + tree indexing.

    Leading axis S is sharded over the mesh ``scen`` axis; everything else is
    replicated.  ``onehot`` is (S, K, N) node membership (nid one-hot), the
    matmul form of per-node sub-communicators.

    For a shared-A batch (``ScenarioBatch.A_shared``), ``A`` is the single
    (m, n) matrix REPLICATED across the mesh — scenario data stays sharded,
    and the shared-A solver's matmuls against the replicated matrix shard
    naturally on the scenario axis under jit auto-partitioning.
    """

    c: jax.Array        # (S, n)
    q2: jax.Array       # (S, n)
    A: jax.Array        # (S, m, n) — or (m, n) replicated when shared
    cl: jax.Array       # (S, m)
    cu: jax.Array       # (S, m)
    lb: jax.Array       # (S, n)
    ub: jax.Array       # (S, n)
    const: jax.Array    # (S,)
    probs: jax.Array    # (S,)
    onehot: jax.Array   # (S, K, N)
    nid_sk: jax.Array   # (S, K) node id per nonant slot


class PHState(NamedTuple):
    """Per-iteration PH carry (all scenario-sharded)."""

    W: jax.Array        # (S, K)
    xbars: jax.Array    # (S, K)
    rho: jax.Array      # (S, K)
    x: jax.Array        # (S, n) last solution
    z: jax.Array        # (S, m) ADMM aux
    y: jax.Array        # (S, m) ADMM dual
    yx: jax.Array       # (S, n) bound dual


class PHStepOut(NamedTuple):
    conv: jax.Array       # scalar: prob-weighted L1 deviation from xbar
    eobj: jax.Array       # scalar: expected objective at current x
    pri_res: jax.Array    # (S,)
    dua_res: jax.Array    # (S,)
    iters: jax.Array      # scalar: ADMM sweeps the subproblem solve used
    # (batch max; feeds the FLOP-model MFU accounting — solvers/flops.py)


# ---------------------------------------------------------------------------
# Rule-driven placement (ROADMAP item 1; the match_partition_rules /
# shard-and-gather pattern of SNIPPETS [3] under the pjit/GSPMD mesh
# semantics of [1]).  One declarative table maps EVERY PHArrays / PHState
# leaf — and therefore every megastep scan carry, which is a PHState — to
# its PartitionSpec by leaf-path regex, instead of per-field ad-hoc
# device_put calls scattered through shard_batch/init_state.  Adding a
# field to either NamedTuple without a matching rule is a loud error, not
# a silently-replicated (S, ...) array: at S=10^4-10^5 one unsharded
# per-scenario leaf is the difference between O(S/ndev) and O(S) HBM.
# ---------------------------------------------------------------------------
def ph_partition_rules(axis: str = "scen", row_axis: str | None = None,
                       shared: bool = False, tenant: bool = False) -> list:
    """[(leaf-path regex, PartitionSpec)] for one mesh posture.

    ``shared``: the batch carries one (m, n) ``A_shared`` — A is replicated
    (or row-sharded over ``row_axis`` on a 2-D mesh, with the (S, m)
    row-state leaves sharded on both axes); dense per-scenario batches
    shard A's leading scenario axis like every other leaf.  First match
    wins, so the specific rows precede the catch-all scenario rule.

    ``tenant``: the TENANT-BATCHED posture (continuous batching,
    doc/serving.md): leaves carry a leading tenant axis — (T, S, ...)
    instead of (S, ...) — and sharding is SCENARIO-WITHIN-TENANT: the
    tenant axis is never partitioned (each slot's scenario rows must stay
    whole so per-tenant masked reductions never cross a device boundary
    mid-slot), the scenario axis shards exactly as in the solo posture.
    Every scenario-leading spec gains a leading ``None``; engine-shaped
    leaves (a replicated shared A) are tenant-stacked but otherwise
    unchanged.
    """
    scen = P(axis)
    if shared:
        A_spec = P(row_axis, None) if row_axis else P()
        row = P(axis, row_axis) if row_axis else scen
    else:
        A_spec, row = scen, scen
    rules = [
        # constraint matrix: the one leaf whose layout depends on the
        # engine (dense stack / replicated shared / SparseA sub-leaves)
        (r"(^|/)A(/|$)", A_spec),
        # (S, m) row-state: constraint bounds + ADMM row iterates
        (r"(^|/)(cl|cu|z|y)$", row),
        # every remaining per-scenario leaf: (S, n), (S, K), (S, K, N), (S,)
        (r"(^|/)(c|q2|lb|ub|const|probs|onehot|nid_sk)$", scen),
        (r"(^|/)(W|xbars|rho|x|yx)$", scen),
    ]
    if tenant:
        # scenario-within-tenant: prepend an UNSHARDED tenant dim to every
        # spec that leads with the scenario axis (the engine-dependent A
        # spec keeps its own layout — a tenant-stacked replicated A simply
        # gains an unsharded leading dim through the same transform)
        rules = [(r, P(None, *s)) for r, s in rules]
    return rules


def _leaf_path(path) -> str:
    parts = []
    for p in path:
        name = getattr(p, "name", None)
        if name is None:
            name = getattr(p, "key", getattr(p, "idx", ""))
        parts.append(str(name))
    return "/".join(parts)


def match_partition_rules(rules, tree):
    """Pytree of PartitionSpec matching each leaf of ``tree`` against
    ``rules`` by its slash-joined path (the SNIPPETS [3] idiom).  Scalars
    never partition; a leaf no rule matches raises — an unplaced leaf is
    a placement-table bug, not a default."""
    from jax.tree_util import tree_flatten_with_path, tree_unflatten

    leaves, treedef = tree_flatten_with_path(tree)

    def pick(path, leaf):
        if np.ndim(leaf) == 0 or np.size(leaf) == 1:
            return P()
        name = _leaf_path(path)
        for rule, spec in rules:
            if re.search(rule, name) is not None:
                return spec
        raise ValueError(f"no partition rule matches leaf {name!r}")

    return tree_unflatten(treedef, [pick(p, l) for p, l in leaves])


def ph_shardings(mesh: Mesh, tree, axis: str = "scen",
                 row_axis: str | None = None, shared: bool = False,
                 tenant: bool = False):
    """Pytree of :class:`NamedSharding` for ``tree`` (a PHArrays, a
    PHState, or any sub-pytree of their leaves) under the placement
    table.  THE single source of wheel-state placement: shard_batch,
    init_state and the shard-read checkpoint restore all derive their
    shardings here, so they cannot drift.  ``tenant`` selects the
    scenario-within-tenant posture for (T, S, ...)-stacked trees."""
    specs = match_partition_rules(
        ph_partition_rules(axis, row_axis, shared, tenant), tree)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def num_ghosts(S: int, mesh: Mesh, axis: str = "scen") -> int:
    """Ghost scenarios appended so S fills the mesh axis evenly (0 when S
    already divides).  Ghosts are zero-probability copies of scenario 0
    with ZERO node membership: inert in every psum-lowered reduction
    (xbar/xsqbar numerators AND denominators, conv, eobj), so an uneven
    S=7 on a 4-device mesh is exact, not approximately padded."""
    return (-int(S)) % int(mesh.shape[axis])


def _node_xbar(onehot, probs, xk):
    """Per-node weighted mean of nonants; per-scenario gather back.

    The contraction over the scenario axis is the Allreduce analogue
    (phbase.py:75-87): under a sharded-in jit, XLA emits one psum per einsum.
    """
    p = probs[:, None]
    num = jnp.einsum("skn,sk->nk", onehot, p * xk)
    sqnum = jnp.einsum("skn,sk->nk", onehot, p * xk * xk)
    den = jnp.einsum("skn,sk->nk", onehot, jnp.broadcast_to(p, xk.shape))
    den = jnp.maximum(den, jnp.finfo(den.dtype).tiny)
    return num / den, sqnum / den


def _gather_per_scenario(xbar_nk, nid_sk):
    K = nid_sk.shape[1]
    kidx = jnp.arange(K)[None, :]
    return xbar_nk[nid_sk, kidx]


def _solver_fns_for(st: ADMMSettings, mesh, axis):
    """(shared_refresh, shared_frozen, dense_refresh, dense_frozen) for one
    settings variant; dense fns are shard_mapped when on a mesh."""
    def shared_refresh(q, q2, A, cl, cu, lb, ub, x, z, y, yx):
        with jax.default_matmul_precision(st.matmul_precision):
            return shared_admm._solve_shared_impl(
                q, q2, A, cl, cu, lb, ub, st, (x, z, y, yx),
                want_factors=True)

    def shared_frozen(q, q2, A, cl, cu, lb, ub, x, z, y, yx, factors):
        with jax.default_matmul_precision(st.matmul_precision):
            return shared_admm._solve_shared_frozen_impl(
                q, q2, A, cl, cu, lb, ub, factors, (x, z, y, yx), st)

    def local_refresh(q, q2, A, cl, cu, lb, ub, x, z, y, yx):
        with jax.default_matmul_precision(st.matmul_precision):
            return admm._solve_impl(
                q, q2, A, cl, cu, lb, ub, st, (x, z, y, yx),
                want_factors=True)

    def local_frozen(q, q2, A, cl, cu, lb, ub, x, z, y, yx, factors):
        with jax.default_matmul_precision(st.matmul_precision):
            return admm._solve_frozen_impl(
                q, q2, A, cl, cu, lb, ub, factors, (x, z, y, yx), st)

    if mesh is not None:
        sp = jax.sharding.PartitionSpec(axis)
        sol_spec = admm.BatchSolution(
            *([sp] * 8), raw=(sp, sp, sp, sp), narrow=sp, swept=sp)
        fac_spec = admm.Factors(*([sp] * 7))
        refresh_solve = _shard_map(
            local_refresh, mesh, in_specs=(sp,) * 11,
            out_specs=(sol_spec, fac_spec),
        )
        frozen_solve = _shard_map(
            local_frozen, mesh,
            in_specs=(sp,) * 11 + (fac_spec,),
            out_specs=sol_spec,
        )
    else:
        refresh_solve, frozen_solve = local_refresh, local_frozen
    return shared_refresh, shared_frozen, refresh_solve, frozen_solve


def _ph_objective(arr, state, prox_on, idx, settings):
    dt = settings.jdtype()
    W, xbars, rho = (state.W.astype(dt), state.xbars.astype(dt),
                     state.rho.astype(dt))
    prox_on = jnp.asarray(prox_on, dt)
    q = arr.c.astype(dt).at[:, idx].add(W - prox_on * rho * xbars)
    q2 = arr.q2.astype(dt).at[:, idx].add(prox_on * rho)
    return q, q2, W, rho


def _ph_finish(arr, state, sol, W, rho, idx):
    xk = sol.x[:, idx]
    xbar_nk, _ = _node_xbar(arr.onehot, arr.probs, xk)
    new_xbars = _gather_per_scenario(xbar_nk, arr.nid_sk)
    new_W = W + rho * (xk - new_xbars)
    dev = jnp.abs(xk - new_xbars).mean(axis=1)
    conv = arr.probs @ dev
    lin = jnp.einsum("sn,sn->s", arr.c, sol.x)
    quad = 0.5 * jnp.einsum("sn,sn->s", arr.q2, sol.x * sol.x)
    eobj = arr.probs @ (lin + quad + arr.const)
    new_state = PHState(
        W=new_W, xbars=new_xbars, rho=rho,
        x=sol.x, z=sol.z, y=sol.y, yx=sol.yx,
    )
    return new_state, PHStepOut(conv, eobj, sol.pri_res, sol.dua_res,
                                jnp.max(sol.iters))


def make_ph_step(nonant_idx: np.ndarray, settings: ADMMSettings,
                 mesh: Mesh | None = None, axis: str = "scen"):
    """Back-compat single-step API: the adaptive (refresh) step of
    :func:`make_ph_step_pair`, with the factors dropped.  One compiled
    program per (shapes, settings); PH iterations re-enter it with new state
    only — the persistent-solver analogue (spopt.py:129-144)."""
    refresh, _ = make_ph_step_pair(nonant_idx, settings, mesh, axis)

    def step(state: PHState, arr: PHArrays, prox_on):
        new_state, out, _ = refresh(state, arr, prox_on)
        return new_state, out

    return step


def make_ph_step_pair(nonant_idx: np.ndarray, settings: ADMMSettings,
                      mesh: Mesh | None = None, axis: str = "scen"):
    """(refresh_step, frozen_step) — the factorization-amortized PH iteration.

    ``refresh_step(state, arr, prox_on) -> (state, out, factors)`` runs the
    full adaptive solve (Ruiz + rho adaptation + factorizations + optional
    polish) and returns the final :class:`~tpusppy.solvers.admm.Factors`.
    ``frozen_step(state, arr, prox_on, factors) -> (state, out)`` reuses them:
    no factorization in the program at all, so the steady-state PH iteration
    is pure batched matvec sweeps (the MXU path).  PH leaves (A, q2, bounds)
    unchanged between iterations — only q moves — so factors stay valid; the
    residual-driven while_loop still guards accuracy, and a periodic refresh
    re-adapts rho (see :func:`run_ph`'s ``refresh_every``).

    The engine is picked PER TRACE from ``arr.A.ndim`` (jit specializes on
    shapes, so the branch is free): 3-D A runs the dense per-scenario solver
    (shard_mapped over the mesh), 2-D A the shared-A solver — invoked
    WITHOUT shard_map, under jit auto-partitioning: its cross-scenario
    reductions (shared-rho adaptation, the all-done termination vote) lower
    to psums over the mesh, so every device sees the SAME shared factors and
    per-device factor divergence is structurally impossible.
    """
    idx = jnp.asarray(nonant_idx)
    # executable-cache identity of the single-dispatch step programs:
    # everything baked into the trace that the call signature can't show
    _aot_extra = (settings, axis, aot_cache.mesh_fingerprint(mesh),
                  aot_cache.array_digest(nonant_idx))

    def _solver_fns(st: ADMMSettings):
        return _solver_fns_for(st, mesh, axis)

    shared_refresh, shared_frozen, refresh_solve, frozen_solve = \
        _solver_fns(settings)

    def _objective(arr, state, prox_on):
        return _ph_objective(arr, state, prox_on, idx, settings)

    def _finish(arr, state, sol, W, rho):
        return _ph_finish(arr, state, sol, W, rho, idx)

    @jax.jit
    def refresh_step_1(state: PHState, arr: PHArrays, prox_on):
        q, q2, W, rho = _objective(arr, state, prox_on)
        solve = shared_refresh if arr.A.ndim == 2 else refresh_solve
        sol, factors = solve(
            q, q2, arr.A, arr.cl, arr.cu, arr.lb, arr.ub,
            state.x, state.z, state.y, state.yx,
        )
        new_state, out = _finish(arr, state, sol, W, rho)
        return new_state, out, factors

    @jax.jit
    def frozen_step_1(state: PHState, arr: PHArrays, prox_on, factors):
        q, q2, W, rho = _objective(arr, state, prox_on)
        solve = shared_frozen if arr.A.ndim == 2 else frozen_solve
        sol = solve(
            q, q2, arr.A, arr.cl, arr.cu, arr.lb, arr.ub,
            state.x, state.z, state.y, state.yx, factors,
        )
        new_state, out = _finish(arr, state, sol, W, rho)
        return new_state, out

    # AOT executable cache (tpusppy/solvers/aot.py): the single-dispatch
    # step programs are exactly the iter0/refresh cold-start cost — a
    # repeated or resumed run deserializes them instead of recompiling.
    # Strict passthrough when TPUSPPY_AOT_CACHE is disarmed.
    refresh_step_1 = aot_cache.cached_program(
        refresh_step_1, "ph_refresh", key_extra=_aot_extra)
    frozen_step_1 = aot_cache.cached_program(
        frozen_step_1, "ph_frozen", key_extra=_aot_extra)

    # ---- segmented dispatch (shapes too big for one program execution) ----

    @jax.jit
    def _prep_jit(state: PHState, arr: PHArrays, prox_on):
        return _objective(arr, state, prox_on)

    @jax.jit
    def _finish_jit(state: PHState, arr: PHArrays, sol, W, rho):
        return _finish(arr, state, sol, W, rho)

    seg_cache: dict = {}

    def _seg_programs(seg_r, seg_f):
        key = (seg_r, seg_f)
        if key not in seg_cache:
            st_r = dataclasses.replace(settings, max_iter=seg_r)
            st_f = segmented_solvers.seg_settings(settings, seg_f)
            sr, _, lr, _ = _solver_fns(st_r)
            _, sf, _, lf = _solver_fns(st_f)

            @jax.jit
            def refresh_solve_seg(q, q2, arr: PHArrays, warm):
                solve = sr if arr.A.ndim == 2 else lr
                x, z, y, yx = warm
                return solve(q, q2, arr.A, arr.cl, arr.cu, arr.lb, arr.ub,
                             x, z, y, yx)

            @jax.jit
            def frozen_solve_seg(q, q2, arr: PHArrays, warm, factors):
                solve = sf if arr.A.ndim == 2 else lf
                x, z, y, yx = warm
                return solve(q, q2, arr.A, arr.cl, arr.cu, arr.lb, arr.ub,
                             x, z, y, yx, factors)

            # short polishing finale for the dense path (single-dispatch
            # refresh polishes; frozen continuations don't — this restores
            # parity from the converged iterate without re-factorizing)
            ce = max(1, settings.check_every)
            st_p = dataclasses.replace(settings, max_iter=2 * ce)

            def local_polish(q, q2, A, cl, cu, lb, ub, x, z, y, yx,
                             factors):
                with jax.default_matmul_precision(st_p.matmul_precision):
                    return admm._solve_frozen_impl(
                        q, q2, A, cl, cu, lb, ub, factors, (x, z, y, yx),
                        st_p, polish=True)

            if mesh is not None:
                sp = jax.sharding.PartitionSpec(axis)
                sol_spec = admm.BatchSolution(
                    *([sp] * 8), raw=(sp, sp, sp, sp), narrow=sp, swept=sp)
                fac_spec = admm.Factors(*([sp] * 7))
                local_polish = _shard_map(
                    local_polish, mesh,
                    in_specs=(sp,) * 11 + (fac_spec,),
                    out_specs=sol_spec,
                )

            @jax.jit
            def polish_solve_seg(q, q2, arr: PHArrays, warm, factors):
                x, z, y, yx = warm
                return local_polish(q, q2, arr.A, arr.cl, arr.cu, arr.lb,
                                    arr.ub, x, z, y, yx, factors)

            seg_cache[key] = (refresh_solve_seg, frozen_solve_seg,
                              polish_solve_seg)
        return seg_cache[key]

    def _segments_for(arr):
        S_dev, n, m, factor_batch, sf = _dispatch_model_params(arr, mesh)
        return _dispatch_segments(S_dev, n, m, settings,
                                  factor_batch=factor_batch,
                                  sparse_factor=sf)

    def _seg_flops_for(arr, seg_f):
        """Per-segment model flops (speculation/dispatch billing unit)."""
        from ..solvers import flops as flops_model
        S_dev, n, m, _, sf = _dispatch_model_params(arr, mesh)
        return flops_model.sweep_flops(S_dev, n, m, sf) * seg_f

    # A mesh spanning several processes cannot make data-dependent host
    # decisions: sol.iters' shards are non-addressable (fetch raises), and
    # even local-shard votes could disagree across processes — different
    # dispatch counts would deadlock the collectives.  Run the full budget
    # deterministically there (and NEVER speculate — continue_frozen
    # disables the pipeline for caller-provided all_done); single-process
    # meshes early-exit normally through the single-fetch stop-stats path,
    # which also unlocks the speculative overlapped continuation.
    multiproc = mesh is not None and len(
        {d.process_index for d in mesh.devices.flat}) > 1

    # plateau stop is data-dependent => multi-process meshes must not use it
    plateau = None if multiproc else settings.segment_plateau_rtol

    def _continue_kw(arr):
        """continue_frozen keywords for this mesh posture."""
        if multiproc:
            return {"all_done": lambda sol: False, "plateau_rtol": None}
        S_dev, n, m, _, _ = _dispatch_model_params(arr, mesh)
        return {"plateau_rtol": plateau,
                "pipeline": segmented_solvers.pipeline_enabled(
                    settings, S_dev, n, m)}

    def refresh_step(state: PHState, arr: PHArrays, prox_on):
        seg_r, seg_f = _segments_for(arr)
        if seg_r >= settings.max_iter and seg_f >= settings.max_iter:
            return refresh_step_1(state, arr, prox_on)
        rsolve, fsolve, psolve = _seg_programs(seg_r, seg_f)
        q, q2, W, rho = _prep_jit(state, arr, prox_on)
        warm = (state.x, state.z, state.y, state.yx)
        sol, factors = rsolve(q, q2, arr, warm)
        sol = segmented_solvers.continue_frozen(
            lambda w: fsolve(q, q2, arr, w, factors), sol, seg_f,
            segmented_solvers.refresh_budget(settings, seg_r),
            seg_flops=_seg_flops_for(arr, seg_f), **_continue_kw(arr))
        if arr.A.ndim == 3 and settings.polish and settings.polish_passes:
            sol = psolve(q, q2, arr, sol.raw, factors)
        new_state, out = _finish_jit(state, arr, sol, W, rho)
        return new_state, out, factors

    def frozen_step(state: PHState, arr: PHArrays, prox_on, factors):
        seg_r, seg_f = _segments_for(arr)
        if seg_r >= settings.max_iter and seg_f >= settings.max_iter:
            return frozen_step_1(state, arr, prox_on, factors)
        _, fsolve, _ = _seg_programs(seg_r, seg_f)
        q, q2, W, rho = _prep_jit(state, arr, prox_on)
        warm = (state.x, state.z, state.y, state.yx)
        sol = fsolve(q, q2, arr, warm, factors)
        if multiproc:
            # deterministic schedule: the first dispatch cannot be checked
            # (non-addressable shards), so the continuation always runs
            # the full budget
            sol = segmented_solvers.continue_frozen(
                lambda w: fsolve(q, q2, arr, w, factors), sol, seg_f,
                settings.max_iter - seg_f, all_done=lambda s: False,
                plateau_rtol=None,
                seg_flops=_seg_flops_for(arr, seg_f))
        else:
            # check_incoming folds the first-dispatch verdict into the
            # (possibly pipelined) continuation's single-fetch protocol
            sol = segmented_solvers.continue_frozen(
                lambda w: fsolve(q, q2, arr, w, factors), sol, seg_f,
                settings.max_iter - seg_f, check_incoming=True,
                seg_flops=_seg_flops_for(arr, seg_f), **_continue_kw(arr))
        new_state, out = _finish_jit(state, arr, sol, W, rho)
        return new_state, out

    return refresh_step, frozen_step


def fused_iteration_cap(arr: PHArrays, settings: ADMMSettings,
                        mesh: Mesh | None = None,
                        refresh_every: int = 16) -> int:
    """Max PH iterations safely fusable into ONE device program for these
    shapes (a multiple of ``refresh_every``; 0 = do not fuse).

    Sized with the same flop model as :func:`dispatch_segments` against the
    per-dispatch budget; shapes that need segmentation get 0 and must use
    the step pair.
    """
    S_dev, n, m, factor_batch, sf = _dispatch_model_params(arr, mesh)
    return segmented_solvers.fused_iteration_budget(
        S_dev, n, m, settings, refresh_every,
        factor_batch=factor_batch,
        eff_flops=_DISPATCH_EFF_FLOPS, target_secs=_DISPATCH_TARGET_SECS,
        sparse_factor=sf)


def make_ph_fused_step(nonant_idx: np.ndarray, settings: ADMMSettings,
                       mesh: Mesh | None = None, axis: str = "scen",
                       chunk: int = 16, refresh_every: int | None = None,
                       donate: bool = True, collect: str = "last"):
    """ONE jitted program running ``chunk`` PH iterations — the latency-proof
    headline path.

    The step pair (:func:`make_ph_step_pair`) pays one device dispatch per PH
    iteration and the host fetch that ends each one blocks the next; for
    small programs (farmer: S=1000, n=44) that round-trip dominates the
    device work.  This factory fuses the
    whole refresh cadence into one program: an adaptive refresh (Ruiz + rho
    adaptation + factorization) at iteration 0 and every ``refresh_every``
    after it, frozen factor-reusing sweeps in between, all inside nested
    ``lax.scan`` — so ``chunk`` PH iterations cost ONE dispatch.  Identical
    trajectory to driving the step pair from the host with the same cadence
    (tests assert this).

    This replaces the reference's per-iteration solve round-trip
    (``mpisppy/spopt.py:226-307``: one ``solve()`` per rank per iteration,
    every iteration a fresh host<->solver exchange) with a single compiled
    multi-iteration program — the XLA-native amortization.

    ``refresh_every`` defaults to ``chunk`` (one refresh at the top).
    ``chunk`` need NOT be a multiple of ``refresh_every``: a trailing
    partial block (refresh + the leftover frozen iterations) preserves the
    host cadence — refreshes land exactly at iteration indices that are
    multiples of ``refresh_every`` within the chunk.  Callers must size
    ``chunk`` within :func:`fused_iteration_cap` (or a measured cap from
    :mod:`tpusppy.tune`) — a fused program past the worker watchdog is
    killed mid-flight, which the host cannot recover.

    ``donate=True`` (default) donates the incoming :class:`PHState` buffers
    to the program (``jax.jit`` ``donate_argnums``): the state is updated
    in place on device instead of round-tripping fresh allocations per
    chunk.  The caller's input state is CONSUMED — rebind it
    (``state, out = fused(state, arr, p)``); reading the old reference
    afterwards raises.  Pass ``donate=False`` for call sites that must
    re-enter the same state object (A/B comparisons).

    ``collect="last"`` returns the LAST iteration's :class:`PHStepOut`;
    ``collect="trace"`` returns the full per-iteration trace (leaves gain a
    leading ``chunk`` axis), carried device-side so a measurement window of
    many chunks needs ONE host fetch at the end instead of per-iteration
    conv/eobj syncs.

    Returns ``fused(state, arr, prox_on) -> (state, out)``.
    """
    if refresh_every is None:
        refresh_every = chunk
    if chunk < 1 or refresh_every < 1:
        raise ValueError(
            f"chunk ({chunk}) and refresh_every ({refresh_every}) must be "
            f">= 1")
    if collect not in ("last", "trace"):
        raise ValueError(f"collect must be 'last' or 'trace': {collect!r}")
    n_full, rem = divmod(chunk, refresh_every)
    idx = jnp.asarray(nonant_idx)
    shared_refresh, shared_frozen, refresh_solve, frozen_solve = \
        _solver_fns_for(settings, mesh, axis)

    @functools.partial(jax.jit, donate_argnums=(0,) if donate else ())
    def fused(state: PHState, arr: PHArrays, prox_on):
        def block_outs(state, length):
            """One refresh + (length-1) frozen iterations; outs stacked
            along a leading ``length`` axis (the device-side trace)."""
            q, q2, W, rho = _ph_objective(arr, state, prox_on, idx, settings)
            rsolve = shared_refresh if arr.A.ndim == 2 else refresh_solve
            sol, factors = rsolve(
                q, q2, arr.A, arr.cl, arr.cu, arr.lb, arr.ub,
                state.x, state.z, state.y, state.yx)
            state, out0 = _ph_finish(arr, state, sol, W, rho, idx)

            def frozen_iter(st, _):
                q, q2, W, rho = _ph_objective(arr, st, prox_on, idx,
                                              settings)
                fsolve = shared_frozen if arr.A.ndim == 2 else frozen_solve
                sol = fsolve(q, q2, arr.A, arr.cl, arr.cu, arr.lb, arr.ub,
                             st.x, st.z, st.y, st.yx, factors)
                return _ph_finish(arr, st, sol, W, rho, idx)

            if length > 1:
                state, outs = jax.lax.scan(
                    frozen_iter, state, None, length=length - 1)
                outs = jax.tree.map(
                    lambda a0, a: jnp.concatenate([a0[None], a]), out0, outs)
            else:
                outs = jax.tree.map(lambda a: a[None], out0)
            return state, outs

        traces = []
        if n_full:
            state, outs = jax.lax.scan(
                lambda s, _: block_outs(s, refresh_every), state, None,
                length=n_full)
            # (n_full, refresh_every, ...) -> (n_full * refresh_every, ...)
            traces.append(jax.tree.map(
                lambda a: a.reshape((-1,) + a.shape[2:]), outs))
        if rem:
            state, outs = block_outs(state, rem)
            traces.append(outs)
        trace = (traces[0] if len(traces) == 1 else jax.tree.map(
            lambda *xs: jnp.concatenate(xs), *traces))
        if collect == "trace":
            return state, trace
        return state, jax.tree.map(lambda a: a[-1], trace)

    # AOT executable cache: the fused multi-iteration program is the
    # dominant bench/wheel cold-start cost (one compile per (chunk,
    # refresh_every) cadence) — repeated and ladder-sibling runs
    # deserialize it in milliseconds instead (tpusppy/solvers/aot.py;
    # passthrough when disarmed)
    return aot_cache.cached_program(
        fused, "ph_fused",
        key_extra=(settings, chunk, refresh_every, bool(donate), collect,
                   axis, aot_cache.mesh_fingerprint(mesh),
                   aot_cache.array_digest(nonant_idx)))


# scalars the in-wheel bound pass appends to the packed measurement
# (lean-pack compatible by construction): [computed flag, Lagrangian outer
# bound, xhat-at-xbar expected objective, feasible probability mass of the
# frozen evaluation, its sweep count (billing)]
BOUND_PACK_LEN = 5


def bound_pack_len(bounds: bool = False, int_sweep: bool = False) -> int:
    """Length of the in-wheel bound tail: :data:`BOUND_PACK_LEN` scalars,
    plus the :data:`~tpusppy.solvers.integer.INT_BOUND_EXTRA` integer
    extras (feasible-candidate count, best candidate index, reduced-cost
    fixed slots, untightened outer) when the batched integer sweep is
    armed (doc/integer.md)."""
    if not bounds:
        return 0
    if int_sweep:
        from ..solvers import integer as integer_solvers

        return BOUND_PACK_LEN + integer_solvers.INT_BOUND_EXTRA
    return BOUND_PACK_LEN


def megastep_measure_len(n_iters: int, S: int, n: int, K: int,
                         pack: str = "full", bounds: bool = False,
                         int_sweep: bool = False) -> int:
    """Length of the packed megastep measurement vector.

    ``pack="lean"`` is the O(1)-host-traffic wheel posture (ROADMAP item
    1): the fetch carries the per-iteration stats plus per-scenario
    residual/done diagnostics ONLY — the (S, n) iterate and the (S, K)
    W/xbars stay device-resident in the returned :class:`PHState`, to be
    fetched explicitly (and billed) at checkpoint/termination boundaries
    instead of every window.

    ``bounds=True`` (in-wheel certification, doc/pipeline.md) appends
    :func:`bound_pack_len` scalars — outer/inner bound evidence computed
    on the window's final device state — compatible with BOTH packs (the
    bound pass emits scalars only); ``int_sweep=True`` is the batched
    integer variant (doc/integer.md) with its longer tail."""
    base = MEGA_STATS * n_iters + 2 + 3 * S
    if pack != "lean":
        base += S * n + 2 * S * K
    return base + bound_pack_len(bounds, int_sweep)


# a megastep's per-iteration stats block, row by row (the solo, bucketed
# and tenant packs alike): the last three are ``admm.width_counters``
_STATS_ROWS = ("conv", "eobj", "pri_max", "dua_max", "iters",
               "all_done") + admm.WIDTH_FIELDS
MEGA_STATS = len(_STATS_ROWS)


def _stats_rows(per) -> dict:
    """A fetched (MEGA_STATS, N) stats block as named per-iteration rows."""
    out = dict(zip(_STATS_ROWS, per))
    out["all_done"] = out["all_done"] != 0.0
    return out


def unpack_bound_tail(out: dict, vec, int_sweep: bool = False) -> dict:
    """Install the in-wheel bound scalars (the trailing
    :func:`bound_pack_len` entries of a ``bounds=True`` measurement) into
    an unpacked measurement dict.  ``bound_computed`` False means the
    window's traced ``bound_live`` flag was off (cadence skip) — the
    other entries are inert zeros then.  ``int_sweep`` additionally
    parses the integer extras (``int_feas_cands``/``int_best_idx``/
    ``int_rcfix_slots``/``bound_outer_base``)."""
    tail_len = bound_pack_len(True, int_sweep)
    tail = np.asarray(vec)[-tail_len:]
    out["bound_computed"] = bool(tail[0])
    out["bound_outer"] = float(tail[1])
    out["bound_inner_obj"] = float(tail[2])
    out["bound_inner_feas"] = float(tail[3])
    out["bound_sweeps"] = float(tail[4])
    if int_sweep:
        out["int_feas_cands"] = int(tail[5])
        out["int_best_idx"] = int(tail[6])
        out["int_rcfix_slots"] = int(tail[7])
        out["bound_outer_base"] = float(tail[8])
    return out


def megastep_unpack(vec, n_iters: int, S: int, n: int, K: int,
                    pack: str = "full", bounds: bool = False,
                    int_sweep: bool = False) -> dict:
    """Split a fetched :func:`make_wheel_megastep` measurement.

    Returns per-iteration arrays (length ``n_iters``; entries past
    ``executed`` are inert zeros — the early-exit mask froze those steps):
    ``conv``, ``eobj``, ``pri_max``, ``dua_max``, ``iters``, ``all_done``,
    ``narrow_sweeps``, ``row_sweeps``, ``full_row_sweeps`` (how much of
    the batch the iteration's solve was still sweeping:
    ``admm.width_counters``); the ``executed`` iteration count; the ``refresh_hit`` flag (an
    iterate failed the in-scan acceptance test — its update was masked
    out, exactly as the serial protocol discards a rejected frozen
    solve, and the host must refresh; index ``executed`` of the per-
    iteration arrays then holds the REJECTED iterate's stats so its
    dispatched sweeps can be billed); and the FINAL executed iterate's
    ``pri``/``dua``/``done`` (S,), ``x`` (S, n), ``W``/``xbars`` (S, K) —
    everything the host wheel reads between termination checks, from ONE
    fetch.  With ``pack="lean"`` the x/W/xbars blocks are absent (device-
    resident state; see :func:`megastep_measure_len`) and those keys are
    not in the dict.  ``bounds=True`` additionally parses the in-wheel
    bound tail (:func:`unpack_bound_tail`)."""
    vec = np.asarray(vec)
    N = n_iters
    per = vec[:MEGA_STATS * N].reshape(MEGA_STATS, N)
    off = MEGA_STATS * N
    executed = int(vec[off])
    refresh_hit = bool(vec[off + 1])
    off += 2
    out = {
        **_stats_rows(per),
        "executed": executed, "refresh_hit": refresh_hit,
        "pri": vec[off:off + S], "dua": vec[off + S:off + 2 * S],
        "done": vec[off + 2 * S:off + 3 * S] != 0.0,
    }
    off += 3 * S
    if bounds:
        out = unpack_bound_tail(out, vec, int_sweep=int_sweep)
    if pack == "lean":
        return out
    out["x"] = vec[off:off + S * n].reshape(S, n)
    off += S * n
    out["W"] = vec[off:off + S * K].reshape(S, K)
    off += S * K
    out["xbars"] = vec[off:off + S * K].reshape(S, K)
    return out


def _bound_pass_terms(arr, st, idx, settings, frozen_fn, factors,
                      feas_tol, int_mask, xhat_threshold):
    """One engine leg of the IN-WHEEL bound pass (doc/pipeline.md
    "In-wheel certification"): probability-weighted partial sums of the
    two certification bounds, computed as fused device contractions on the
    window's final device-resident :class:`PHState` — so a megastep window
    can certify without any spoke device program.

    * OUTER — the Lagrangian dual bound (W on, prox off): the subproblem
      objective ``c + W`` on the nonant columns, evaluated through the
      single-sourced :func:`~tpusppy.solvers.admm.
      dual_objective_with_margin_traced` weak-duality assembly with the
      state's row duals ``y`` (ANY y certifies; the carried duals of a
      near-converged wheel are tight) — the
      ``cylinders.lagrangian_bounder`` semantics without the spoke's own
      batched solve.
    * INNER — xhat-at-xbar: the candidate is the window's consensus
      ``xbars`` (integer nonant slots rounded at ``xhat_threshold``, the
      ``cylinders.xhatxbar_bounder.xbar_candidate`` rule), clamped onto
      the nonant columns and evaluated by ONE batched frozen solve.  The
      clamped problem is solved under the PH-AUGMENTED (q, q2) — on the
      clamped box the augmentation differs from the plain objective only
      on fixed coordinates (a constant), so the minimizer is identical
      AND the window's cached factors match exactly; the reported
      objective is the PLAIN one.  Feasibility is the ``Xhat_Eval`` gate:
      the per-scenario primal residual against ``feas_tol``, emitted as a
      probability mass so the host applies the all-scenarios rule.

    Returns ``(outer, inner_obj, feas_mass, sweeps)`` scalars; the
    bucketed kernel sums the per-bucket contributions (probs are
    global-tree slices there, so the sums compose exactly)."""
    dt = settings.jdtype()
    W = st.W.astype(dt)
    qL = arr.c.astype(dt).at[:, idx].add(W)
    packed = admm.dual_objective_with_margin_traced(
        qL, arr.q2, arr.A, arr.cl, arr.cu, arr.lb, arr.ub,
        st.y.astype(dt), st.x.astype(dt))
    outer = arr.probs @ (packed[0].astype(dt) - packed[1].astype(dt)
                         + arr.const)
    cand = st.xbars.astype(dt)
    if int_mask is not None and int_mask.any():
        cand = jnp.where(jnp.asarray(int_mask)[None, :],
                         jnp.floor(cand + (1.0 - xhat_threshold)), cand)
    # the `xbar_candidate` bounds clip: consensus means carry ADMM
    # tolerance noise (u = -4e-8), and a clamped column eps outside its
    # box poisons every coupled row (p <= pmax*u < 0 vs p >= 0) — the
    # frozen evaluation would read a 1e-8 rounding artifact as batchwide
    # infeasibility
    cand = jnp.clip(cand, arr.lb.astype(dt)[:, idx],
                    arr.ub.astype(dt)[:, idx])
    lb2 = arr.lb.at[:, idx].set(cand)
    ub2 = arr.ub.at[:, idx].set(cand)
    q, q2, _, _ = _ph_objective(arr, st, 1.0, idx, settings)
    x0 = st.x.astype(dt).at[:, idx].set(cand)
    sol = frozen_fn(q, q2, arr.A, arr.cl, arr.cu, lb2, ub2,
                    x0, st.z, st.y, st.yx, factors)
    lin = jnp.einsum("sn,sn->s", arr.c.astype(dt), sol.x)
    quad = 0.5 * jnp.einsum("sn,sn->s", arr.q2.astype(dt),
                            sol.x * sol.x)
    inner_obj = arr.probs @ (lin + quad + arr.const)
    feas = arr.probs @ (sol.pri_res < jnp.asarray(feas_tol, dt)).astype(dt)
    return (outer.astype(dt), inner_obj.astype(dt), feas.astype(dt),
            jnp.max(sol.iters).astype(dt))


def make_wheel_megastep(nonant_idx: np.ndarray, settings: ADMMSettings,
                        mesh: Mesh | None = None, axis: str = "scen",
                        n_iters: int = 8, donate: bool = True,
                        pack: str = "full", bounds: bool = False,
                        int_nonants: np.ndarray | None = None,
                        xhat_threshold: float = 0.5,
                        int_rounding: tuple | None = None,
                        int_cols: np.ndarray | None = None,
                        rcfix_slack: float = 1e-5,
                        int_rcfix: bool = True):
    """ONE jitted program running up to ``n_iters`` FROZEN wheel iterations
    — the device-resident wheel megakernel (ROADMAP item 4).

    Each scan step is a full PH wheel iteration: augmented objective from
    the carried (W, xbars, rho), the frozen factor-reusing subproblem
    sweep (dense, shared-A, or SparseA/structured — picked per trace from
    ``arr.A``), and the PH outer update (``Compute_Xbar``/``Update_W``/
    convergence, :mod:`tpusppy.phbase` ported to the pure device form
    ``_ph_finish`` — under a mesh its scenario-axis contractions lower to
    psum trees, so N iterations cost ZERO per-iteration host traffic).
    The program returns the new device state plus ONE packed measurement
    vector (:func:`megastep_unpack`): per-iteration stats, the executed
    count, and the final iterate — the host fetches once per megastep
    instead of once per iteration.

    In-scan early exit: the scan always runs ``n_iters`` steps, but once
    the PH convergence test fires (``conv < convthresh``, evaluated after
    each iteration exactly like the serial loop's break) — or the step
    index reaches the traced ``n_live`` budget — the remaining steps take
    the dead ``lax.cond`` branch: no sweeps, state passes through
    untouched.  The packed measurement records the true stopping
    iteration, so results are identical to the serial per-iteration
    protocol that broke at the same iteration, and a single compiled
    program serves any executed count <= ``n_iters``.

    In-scan ACCEPTANCE (the serial frozen protocol's per-iteration test,
    ``spopt._solve_amortized``): an iterate that is neither eps-converged
    nor within the traced ``accept_tol`` residual ladder is DISCARDED —
    its state update is masked out and the window stops with
    ``refresh_hit`` set, exactly as the serial path throws away a
    rejected frozen solve and re-solves adaptively.  The host then runs
    that iteration through the legacy refresh path, so trajectories stay
    identical to serial even when factor aging degrades the frozen
    residuals mid-window.  Pass ``accept_tol=inf`` to disable (raw
    N-iteration fusion).

    Callers must size ``n_iters`` within
    :func:`tpusppy.solvers.segmented.megastep_cap` (a megastep is N
    iterations of work inside one dispatch budget)
    and bill executed iterations via
    :func:`~tpusppy.solvers.segmented.bill_megastep`.  SINGLE-CONTROLLER
    fetch contract: the packed measurement is fetched by the host, which
    needs addressable shards (same restriction as the segmented
    stop-stats protocol).

    ``donate=True`` donates the incoming :class:`PHState` (the caller
    rebinds); pass False for A/B comparisons re-entering one state.

    ``pack="lean"`` drops the final iterate's x/W/xbars from the packed
    measurement (:func:`megastep_measure_len`): those leaves live on in
    the RETURNED device state, making the per-window host traffic O(S)
    diagnostics instead of O(S·n) state — the big-S wheel fetches full
    state only at checkpoint/termination boundaries
    (:meth:`tpusppy.phbase.PHBase._sync_host_state`).

    ``bounds=True`` makes the megastep SELF-CERTIFYING (in-wheel
    certification, doc/pipeline.md): after the scan, an optional bound
    pass (:func:`_bound_pass_terms` — the Lagrangian outer bound and the
    xhat-at-xbar inner bound as fused contractions on the final device
    state) appends :data:`BOUND_PACK_LEN` scalars to the packed
    measurement (lean-pack compatible).  The pass is gated by the TRACED
    ``bound_live`` flag — a cadence skip takes a dead ``lax.cond`` branch
    at zero cost inside the SAME compiled program, so the bound cadence
    never multiplies compiles or AOT cache entries.  ``int_nonants`` is
    the (K,) integer mask of nonant slots (candidate rounding at
    ``xhat_threshold``); both are baked constants and ride the AOT key.

    ``int_rounding`` (a tuple of rounding thresholds) arms the BATCHED
    INTEGER sweep (doc/integer.md) for ``bounds=True`` families with
    integer nonants: the bound pass becomes the vmapped best-of-C
    rounding ladder + SLAM slams with device argmin over feasible
    candidates, plus reduced-cost fixing from the frozen duals and a
    tightened Lagrangian outer bound
    (:func:`tpusppy.solvers.integer.integer_bound_pass`); the bound tail
    grows by :data:`~tpusppy.solvers.integer.INT_BOUND_EXTRA` scalars.
    ``int_cols`` is the (n,) mask of ALL integer columns (the
    reduced-cost fixing scope; defaults to integer nonant slots only).
    ``int_rcfix=False`` disables the reduced-cost fixing +
    re-certification (MANDATORY for families with second-stage integer
    columns: the candidate evaluation relaxes them, so its value is not
    a valid integer-minimum upper bound for the fixing argument — see
    :func:`tpusppy.solvers.integer.integer_bound_pass`).  Families
    WITHOUT integer nonants ignore all the integer knobs and compile
    the byte-identical legacy bound pass (the warm-serving zero-miss
    contract — pinned by test).

    Returns ``mega(state, arr, prox_on, factors, convthresh, n_live,
    accept_tol) -> (state, packed)`` — with ``bounds=True`` the signature
    gains trailing ``(bound_live, feas_tol)`` arguments.
    """
    if n_iters < 1:
        raise ValueError(f"n_iters ({n_iters}) must be >= 1")
    if pack not in ("full", "lean"):
        raise ValueError(f"pack must be 'full' or 'lean': {pack!r}")
    idx = jnp.asarray(nonant_idx)
    int_mask = (None if int_nonants is None
                else np.asarray(int_nonants, dtype=bool))
    # the integer sweep exists in the program ONLY when the family has
    # integer nonants AND a rounding ladder was requested — a bounds=True
    # megastep without integer slots stays byte-identical to the legacy
    # program whatever the integer knobs say (warm serving stays
    # zero-miss; pinned by test)
    int_sweep = bool(bounds and int_mask is not None and int_mask.any()
                     and int_rounding)
    int_thresholds = tuple(float(t) for t in (int_rounding or ()))
    from ..solvers import integer as integer_solvers
    tail_len = bound_pack_len(True, int_sweep)
    if int_sweep:
        int_cols_mask = (np.asarray(int_cols, dtype=bool)
                         if int_cols is not None else None)
        int_mask_arr = jnp.asarray(int_mask)
    _, shared_frozen, _, frozen_solve = _solver_fns_for(settings, mesh, axis)

    @functools.partial(jax.jit, donate_argnums=(0,) if donate else ())
    def mega(state: PHState, arr: PHArrays, prox_on, factors, convthresh,
             n_live, accept_tol, bound_live=False, feas_tol=1e-3):
        dt = settings.jdtype()
        S = arr.c.shape[0]
        n_live_t = jnp.asarray(n_live, jnp.int32)
        thresh = jnp.asarray(convthresh, dt)
        tol = jnp.asarray(accept_tol, dt)

        def body(carry, k):
            st, pri, dua, done_s, executed, stopped, refresh = carry
            live = (~stopped) & (k < n_live_t)

            def live_fn(op):
                st, pri, dua, done_s, executed, stopped, refresh = op
                q, q2, W, rho = _ph_objective(arr, st, prox_on, idx,
                                              settings)
                fsolve = (shared_frozen if arr.A.ndim == 2
                          else frozen_solve)
                sol = fsolve(q, q2, arr.A, arr.cl, arr.cu, arr.lb,
                             arr.ub, st.x, st.z, st.y, st.yx, factors)
                # the serial acceptance test (NaN/inf residuals — e.g. a
                # divergence-frozen scenario — fail it too, so a rejected
                # iterate can never poison the carried state)
                ok = jnp.all(sol.done) | jnp.all(
                    (sol.pri_res <= tol) & (sol.dua_res <= tol))
                new_st, out = _ph_finish(arr, st, sol, W, rho, idx)
                stats = jnp.stack([
                    out.conv.astype(dt), out.eobj.astype(dt),
                    jnp.max(sol.pri_res).astype(dt),
                    jnp.max(sol.dua_res).astype(dt),
                    jnp.max(sol.iters).astype(dt),
                    jnp.all(sol.done).astype(dt),
                    *admm.width_counters(sol).astype(dt)])
                # rejected iterate: mask the whole STATE update (the
                # serial protocol discards the failed frozen solve and
                # re-solves adaptively — the host's refresh does that).
                # Its stats row stays recorded at index ``executed`` so
                # the host can BILL the dispatched-but-discarded sweeps.
                sel = lambda a, b: jnp.where(ok, a, b)
                new_st = jax.tree.map(sel, new_st, st)
                # the serial loop breaks AFTER the iteration whose conv
                # crossed the threshold: this iteration's state is kept,
                # later ones are masked
                return ((new_st, sel(sol.pri_res, pri),
                         sel(sol.dua_res, dua), sel(sol.done, done_s),
                         executed + ok.astype(jnp.int32),
                         stopped | (ok & (out.conv < thresh)) | ~ok,
                         refresh | ~ok),
                        stats)

            def dead_fn(op):
                return op, jnp.zeros((MEGA_STATS,), dt)

            return jax.lax.cond(
                live, live_fn, dead_fn,
                (st, pri, dua, done_s, executed, stopped, refresh))

        inf = jnp.full((S,), jnp.inf, dt)
        carry0 = (state, inf, inf, jnp.zeros((S,), bool),
                  jnp.zeros((), jnp.int32), jnp.zeros((), bool),
                  jnp.zeros((), bool))
        (st, pri, dua, done_s, executed, _, refresh), stats = jax.lax.scan(
            body, carry0, jnp.arange(n_iters, dtype=jnp.int32))
        parts = [
            stats.T.reshape(-1),          # [conv|eobj|pri|dua|iters|done]xN
            executed.astype(dt)[None], refresh.astype(dt)[None],
            pri.astype(dt), dua.astype(dt), done_s.astype(dt),
        ]
        if pack == "full":
            parts += [st.x.astype(dt).reshape(-1),
                      st.W.astype(dt).reshape(-1),
                      st.xbars.astype(dt).reshape(-1)]
        if bounds:
            fsolve = shared_frozen if arr.A.ndim == 2 else frozen_solve

            if int_sweep:
                # fixing scope: all integer columns when the caller
                # supplied them, else the integer nonant slots only
                if int_cols_mask is not None:
                    cols = jnp.asarray(int_cols_mask)
                else:
                    cols = jnp.zeros(arr.c.shape[1], bool).at[idx].set(
                        int_mask_arr)

                def bounds_on(stf):
                    # PH-augmented objective, prox ON — the factors match
                    # exactly (the _bound_pass_terms argument)
                    q, q2, _, _ = _ph_objective(arr, stf, 1.0, idx,
                                                settings)
                    return integer_solvers.integer_bound_pass(
                        arr, stf, idx, q, q2, fsolve, factors, feas_tol,
                        dt, int_mask_arr, int_thresholds, cols,
                        rcfix_slack, rcfix_enabled=bool(int_rcfix))
            else:
                def bounds_on(stf):
                    outer, inner, feas, sweeps = _bound_pass_terms(
                        arr, stf, idx, settings, fsolve, factors,
                        feas_tol, int_mask, xhat_threshold)
                    return jnp.stack(
                        [jnp.ones((), dt), outer, inner, feas, sweeps])

            parts.append(jax.lax.cond(
                jnp.asarray(bound_live, bool),
                bounds_on, lambda _: jnp.zeros((tail_len,), dt), st))
        return st, jnp.concatenate(parts)

    # AOT executable cache: one megakernel compile per width N — resumed
    # and repeated wheels load the serialized executable instead
    # (tpusppy/solvers/aot.py; passthrough when disarmed).  The bound-pass
    # variant (and its baked rounding constants) rides the key so warm
    # serving of a self-certifying wheel stays zero-miss.
    return aot_cache.cached_program(
        mega, "wheel_megastep",
        # _STATS_ROWS: the packed vector's layout, which no argument shows
        key_extra=(_STATS_ROWS, settings, n_iters, bool(donate), axis, pack,
                   # the rounding constants exist only in the bounds=True
                   # program — keying them while bounds are off would
                   # recompile a byte-identical megastep over an inert
                   # knob (a warm-serving aot.misses hit).  The integer-
                   # sweep constants (ladder + fixing scope) likewise
                   # ride the key ONLY when the sweep is compiled in: a
                   # no-integer-slots family keys identically whatever
                   # the integer knobs say.
                   (float(xhat_threshold),
                    None if int_mask is None
                    else aot_cache.array_digest(int_mask),
                    (int_thresholds, float(rcfix_slack),
                     bool(int_rcfix),
                     None if int_cols is None
                     else aot_cache.array_digest(
                         np.asarray(int_cols, dtype=bool)))
                    if int_sweep else None)
                   if bounds else None,
                   aot_cache.mesh_fingerprint(mesh),
                   aot_cache.array_digest(nonant_idx)))


def bucketed_megastep_measure_len(n_iters: int, shapes, K: int,
                                  bounds: bool = False,
                                  int_sweep: bool = False) -> int:
    """Length of the bucketed packed measurement (``shapes`` =
    ``[(S_b, n_b), ...]`` per bucket, concatenated in bucket order).
    ``bounds`` appends the :func:`bound_pack_len` in-wheel bound tail
    (``int_sweep`` = the longer batched-integer variant)."""
    S = sum(s for s, _ in shapes)
    return (MEGA_STATS * n_iters + 2 + 3 * S
            + sum(s * n for s, n in shapes) + 2 * S * K
            + bound_pack_len(bounds, int_sweep))


def bucketed_megastep_unpack(vec, n_iters: int, shapes, K: int,
                             bounds: bool = False,
                             int_sweep: bool = False) -> dict:
    """Split a fetched :func:`make_bucketed_wheel_megastep` measurement.

    Global per-iteration stats exactly as :func:`megastep_unpack`; the
    per-scenario blocks come back PER BUCKET (``shapes`` order): ``pri``/
    ``dua``/``done`` are lists of (S_b,) arrays, ``x`` a list of
    (S_b, n_b), ``W``/``xbars`` lists of (S_b, K) — the host scatters
    them through each bucket's scenario-index array.  ``bounds`` parses
    the trailing in-wheel bound tail (:func:`unpack_bound_tail`)."""
    vec = np.asarray(vec)
    N = n_iters
    per = vec[:MEGA_STATS * N].reshape(MEGA_STATS, N)
    off = MEGA_STATS * N
    out = {
        **_stats_rows(per),
        "executed": int(vec[off]), "refresh_hit": bool(vec[off + 1]),
    }
    off += 2
    if bounds:
        out = unpack_bound_tail(out, vec, int_sweep=int_sweep)
    pri, dua, done = [], [], []
    for S_b, _ in shapes:
        pri.append(vec[off:off + S_b])
        dua.append(vec[off + S_b:off + 2 * S_b])
        done.append(vec[off + 2 * S_b:off + 3 * S_b] != 0.0)
        off += 3 * S_b
    out.update(pri=pri, dua=dua, done=done)
    xs = []
    for S_b, n_b in shapes:
        xs.append(vec[off:off + S_b * n_b].reshape(S_b, n_b))
        off += S_b * n_b
    Ws, xbs = [], []
    for S_b, _ in shapes:
        Ws.append(vec[off:off + S_b * K].reshape(S_b, K))
        off += S_b * K
    for S_b, _ in shapes:
        xbs.append(vec[off:off + S_b * K].reshape(S_b, K))
        off += S_b * K
    out.update(x=xs, W=Ws, xbars=xbs)
    return out


def _bucketed_finish(arrs, states, sols, Ws, rhos, idx, dt):
    """The cross-bucket PH outer update as pure device contractions: each
    bucket contributes its node-membership partial sums (its ``onehot``/
    ``probs`` are GLOBAL-tree slices), the per-node averages form once
    globally, and each bucket gathers its scenarios' rows back — under a
    mesh every cross-bucket sum is the same psum tree the homogeneous
    :func:`_node_xbar` lowers to.  Returns (new_states, conv, eobj).

    The bucketed kernel packs FULL measurements only: the lean
    (device-resident, O(1)-host) posture is homogeneous-only today —
    ``_megastep_solve_bucketed`` says so loudly when ``ph_device_state``
    is set on a bucketed family."""
    num = den = None
    xks = []
    for arr, sol in zip(arrs, sols):
        xk = sol.x[:, idx]
        xks.append(xk)
        p = arr.probs[:, None]
        nm = jnp.einsum("skn,sk->nk", arr.onehot, p * xk)
        dn = jnp.einsum("skn,sk->nk", arr.onehot,
                        jnp.broadcast_to(p, xk.shape))
        num = nm if num is None else num + nm
        den = dn if den is None else den + dn
    xbar_nk = num / jnp.maximum(den, 1e-300)
    new_states = []
    conv = jnp.zeros((), dt)
    eobj = jnp.zeros((), dt)
    for arr, st, sol, W, rho, xk in zip(arrs, states, sols, Ws, rhos, xks):
        new_xbars = _gather_per_scenario(xbar_nk, arr.nid_sk)
        new_W = W + rho * (xk - new_xbars)
        dev = jnp.abs(xk - new_xbars).mean(axis=1)
        conv = conv + (arr.probs @ dev).astype(dt)
        lin = jnp.einsum("sn,sn->s", arr.c, sol.x)
        quad = 0.5 * jnp.einsum("sn,sn->s", arr.q2, sol.x * sol.x)
        eobj = eobj + (arr.probs @ (lin + quad + arr.const)).astype(dt)
        new_states.append(PHState(
            W=new_W, xbars=new_xbars, rho=rho,
            x=sol.x, z=sol.z, y=sol.y, yx=sol.yx))
    return tuple(new_states), conv, eobj


def make_bucketed_wheel_megastep(nonant_idx: np.ndarray,
                                 settings: ADMMSettings,
                                 n_iters: int = 8, donate: bool = True,
                                 axis: str = "scen", bounds: bool = False,
                                 int_nonants=None,
                                 xhat_threshold: float = 0.5,
                                 int_rounding: tuple | None = None,
                                 int_cols=None,
                                 rcfix_slack: float = 1e-5,
                                 int_rcfix: bool = True):
    """ONE jitted program running up to ``n_iters`` frozen wheel
    iterations over a BUCKETED (ragged) family — the shape-bucketed twin
    of :func:`make_wheel_megastep`.

    Each scan step runs EVERY bucket's frozen factor-reusing sweep on its
    own compact shapes (one ragged bucket no longer pads the others), then
    the PH outer update couples them: per-node sums accumulate across
    buckets (each bucket's ``onehot``/``probs`` slice the GLOBAL tree),
    the node averages form once, and every bucket gathers its own rows
    back — the scattered host path's Compute_Xbar/Update_W, device-side.
    The early-exit / acceptance masks are GLOBAL (the serial protocol
    evaluates convergence and acceptance on the whole family), and one
    packed measurement (:func:`bucketed_megastep_unpack`) serves the
    window.

    ``nonant_idx`` is the GLOBAL nonant column index array — valid in
    every bucket's column space, exactly as the host path applies its
    globally-assembled augmented objective bucket-sliced.  Callers size
    ``n_iters`` within :func:`~tpusppy.solvers.segmented.megastep_cap_multi`
    (one scan step is the SUM of all buckets' sweeps against the worker
    watchdog).

    ``bounds=True`` appends the in-wheel bound tail exactly like the
    homogeneous kernel: each bucket contributes its probability-weighted
    partial sums (:func:`_bound_pass_terms` — probs/onehot are
    GLOBAL-tree slices, so cross-bucket accumulation is exact), and the
    feasibility mass is global like the acceptance mask.
    ``int_nonants`` is per-bucket (a tuple of (K,) masks — bucketing can
    key on the integer pattern, so slots may differ across buckets).

    Returns ``mega(states, arrs, prox_on, factors, convthresh, n_live,
    accept_tol) -> (states, packed)`` over tuples of per-bucket
    :class:`PHState` / :class:`PHArrays` / factors — with ``bounds=True``
    the signature gains trailing ``(bound_live, feas_tol)``.
    """
    if n_iters < 1:
        raise ValueError(f"n_iters ({n_iters}) must be >= 1")
    idx = jnp.asarray(nonant_idx)
    int_masks = (None if int_nonants is None else
                 tuple(None if m is None else np.asarray(m, dtype=bool)
                       for m in int_nonants))
    # the batched integer sweep arms when ANY bucket has integer nonants
    # and a ladder was requested; candidates are evaluated per bucket and
    # the best-of-C selection is GLOBAL (summed partial objectives) —
    # no-integer families compile the byte-identical legacy pass
    int_sweep = bool(
        bounds and int_rounding and int_masks is not None
        and any(m is not None and m.any() for m in int_masks))
    int_thresholds = tuple(float(t) for t in (int_rounding or ()))
    int_cols_masks = (None if int_cols is None else
                      tuple(None if m is None else np.asarray(m, bool)
                            for m in int_cols))
    from ..solvers import integer as integer_solvers
    tail_len = bound_pack_len(True, int_sweep)
    shared_refresh, shared_frozen, _, frozen_solve = _solver_fns_for(
        settings, None, axis)
    del shared_refresh

    @functools.partial(jax.jit, donate_argnums=(0,) if donate else ())
    def mega(states, arrs, prox_on, factors, convthresh, n_live,
             accept_tol, bound_live=False, feas_tol=1e-3):
        dt = settings.jdtype()
        n_live_t = jnp.asarray(n_live, jnp.int32)
        thresh = jnp.asarray(convthresh, dt)
        tol = jnp.asarray(accept_tol, dt)

        def body(carry, k):
            sts, pris, duas, dones, executed, stopped, refresh = carry
            live = (~stopped) & (k < n_live_t)

            def live_fn(op):
                sts, pris, duas, dones, executed, stopped, refresh = op
                sols = []
                for bi, (arr, st) in enumerate(zip(arrs, sts)):
                    q, q2, _, _ = _ph_objective(arr, st, prox_on, idx,
                                                settings)
                    fsolve = (shared_frozen if arr.A.ndim == 2
                              else frozen_solve)
                    sols.append(fsolve(
                        q, q2, arr.A, arr.cl, arr.cu, arr.lb, arr.ub,
                        st.x, st.z, st.y, st.yx, factors[bi]))
                Ws = [st.W.astype(dt) for st in sts]
                rhos = [st.rho.astype(dt) for st in sts]
                # GLOBAL acceptance: the serial protocol accepts/rejects
                # the whole family's iterate, never a single bucket's
                all_done = jnp.array(True)
                lad = jnp.array(True)
                for sol in sols:
                    all_done = all_done & jnp.all(sol.done)
                    lad = lad & jnp.all(
                        (sol.pri_res <= tol) & (sol.dua_res <= tol))
                ok = all_done | lad
                new_sts, conv, eobj = _bucketed_finish(
                    arrs, sts, sols, Ws, rhos, idx, dt)
                stats = jnp.stack([
                    conv, eobj,
                    jnp.max(jnp.stack(
                        [jnp.max(s.pri_res) for s in sols])).astype(dt),
                    jnp.max(jnp.stack(
                        [jnp.max(s.dua_res) for s in sols])).astype(dt),
                    jnp.max(jnp.stack(
                        [jnp.max(s.iters) for s in sols])).astype(dt),
                    all_done.astype(dt),
                    # the buckets' counters add up (each bucket's full
                    # count is its own sweeps x its own rows)
                    *sum(admm.width_counters(s) for s in sols).astype(dt)])
                sel = lambda a, b: jnp.where(ok, a, b)
                new_sts = jax.tree.map(sel, new_sts, sts)
                new_pris = tuple(sel(s.pri_res, p)
                                 for s, p in zip(sols, pris))
                new_duas = tuple(sel(s.dua_res, d)
                                 for s, d in zip(sols, duas))
                new_dones = tuple(sel(s.done, d)
                                  for s, d in zip(sols, dones))
                return ((new_sts, new_pris, new_duas, new_dones,
                         executed + ok.astype(jnp.int32),
                         stopped | (ok & (conv < thresh)) | ~ok,
                         refresh | ~ok),
                        stats)

            def dead_fn(op):
                return op, jnp.zeros((MEGA_STATS,), dt)

            return jax.lax.cond(
                live, live_fn, dead_fn,
                (sts, pris, duas, dones, executed, stopped, refresh))

        infs = tuple(jnp.full((arr.c.shape[0],), jnp.inf, dt)
                     for arr in arrs)
        falses = tuple(jnp.zeros((arr.c.shape[0],), bool) for arr in arrs)
        carry0 = (states, infs, infs, falses,
                  jnp.zeros((), jnp.int32), jnp.zeros((), bool),
                  jnp.zeros((), bool))
        (sts, pris, duas, dones, executed, _, refresh), stats = \
            jax.lax.scan(body, carry0,
                         jnp.arange(n_iters, dtype=jnp.int32))
        parts = [stats.T.reshape(-1),
                 executed.astype(dt)[None], refresh.astype(dt)[None]]
        for p, d, dn in zip(pris, duas, dones):
            parts += [p.astype(dt), d.astype(dt), dn.astype(dt)]
        parts += [st.x.astype(dt).reshape(-1) for st in sts]
        parts += [st.W.astype(dt).reshape(-1) for st in sts]
        parts += [st.xbars.astype(dt).reshape(-1) for st in sts]
        if bounds:
            if int_sweep:
                def bounds_on(stsf):
                    # per-bucket partial sums of the candidate sweep —
                    # probs/onehot are GLOBAL-tree slices, so summing
                    # composes exactly and the argmin is global.  SLAM
                    # candidates are DROPPED on the bucketed posture: a
                    # per-bucket slam extreme is not nonanticipative
                    # across buckets (candidate_ladder docstring); the
                    # ladder candidates derive from the GLOBAL xbars and
                    # are identical across buckets for shared nodes.
                    S_tot = sum(arr.c.shape[0] for arr in arrs)
                    per = []
                    for bi, (arr, stf) in enumerate(zip(arrs, stsf)):
                        fsolve = (shared_frozen if arr.A.ndim == 2
                                  else frozen_solve)
                        q, q2, _, _ = _ph_objective(arr, stf, 1.0, idx,
                                                    settings)
                        mb = (int_masks[bi] if int_masks is not None and
                              int_masks[bi] is not None
                              else np.zeros(arr.nid_sk.shape[1], bool))
                        per.append((integer_solvers.sweep_partials(
                            arr, stf, idx, q, q2, fsolve, factors[bi],
                            feas_tol, dt, jnp.asarray(mb),
                            int_thresholds, include_slams=False),
                            q, q2, fsolve, mb))
                    inner_c = sum(p[0][0] for p in per)
                    feas_c = sum(p[0][1] for p in per)
                    sweeps_c = functools.reduce(
                        jnp.maximum, (p[0][2] for p in per))
                    slack = jnp.asarray(
                        integer_solvers.feas_slack(S_tot, dt), dt)
                    ok_c = feas_c >= 1.0 - slack
                    best = jnp.argmin(jnp.where(
                        ok_c, inner_c, jnp.asarray(np.inf, dt)))
                    n_feas = jnp.sum(ok_c.astype(dt))
                    outer = base = nfix = jnp.zeros((), dt)
                    sweeps = jnp.max(sweeps_c)
                    for bi, (arr, stf) in enumerate(zip(arrs, stsf)):
                        (res, q, q2, fsolve, mb) = per[bi]
                        _, _, _, u_cs, fm_cs = res
                        if int_rcfix:
                            if int_cols_masks is not None and \
                                    int_cols_masks[bi] is not None:
                                cols = jnp.asarray(int_cols_masks[bi])
                            else:
                                cols = jnp.zeros(
                                    arr.c.shape[1], bool).at[idx].set(
                                    jnp.asarray(mb))
                            ob, obb, nf, swF = \
                                integer_solvers.rc_outer_partials(
                                    arr, stf, idx, q, q2, fsolve,
                                    factors[bi], dt, cols, u_cs[best],
                                    fm_cs[best], rcfix_slack)
                            sweeps = jnp.maximum(sweeps, swF)
                        else:
                            # second-stage integers somewhere in the
                            # family: plain weak duality only (the
                            # fixing argument has no valid u_s)
                            W = stf.W.astype(dt)
                            qL = arr.c.astype(dt).at[:, idx].add(W)
                            packed = \
                                admm.dual_objective_with_margin_traced(
                                    qL, arr.q2.astype(dt), arr.A,
                                    arr.cl, arr.cu, arr.lb.astype(dt),
                                    arr.ub.astype(dt),
                                    stf.y.astype(dt), stf.x.astype(dt))
                            ob = obb = (arr.probs @ (
                                packed[0].astype(dt)
                                - packed[1].astype(dt)
                                + arr.const)).astype(dt)
                            nf = jnp.zeros((), dt)
                        outer = outer + ob
                        base = base + obb
                        nfix = nfix + nf
                    return jnp.stack([
                        jnp.ones((), dt), outer, inner_c[best],
                        feas_c[best], sweeps, n_feas, best.astype(dt),
                        nfix, base])
            else:
                def bounds_on(stsf):
                    outer = inner = feas = jnp.zeros((), dt)
                    sweeps = jnp.zeros((), dt)
                    for bi, (arr, stf) in enumerate(zip(arrs, stsf)):
                        fsolve = (shared_frozen if arr.A.ndim == 2
                                  else frozen_solve)
                        ob, ib, fm, sw = _bound_pass_terms(
                            arr, stf, idx, settings, fsolve, factors[bi],
                            feas_tol,
                            None if int_masks is None else int_masks[bi],
                            xhat_threshold)
                        outer = outer + ob
                        inner = inner + ib
                        feas = feas + fm
                        sweeps = jnp.maximum(sweeps, sw)
                    return jnp.stack(
                        [jnp.ones((), dt), outer, inner, feas, sweeps])

            parts.append(jax.lax.cond(
                jnp.asarray(bound_live, bool),
                bounds_on, lambda _: jnp.zeros((tail_len,), dt),
                sts))
        return sts, jnp.concatenate(parts)

    # AOT executable cache: keyed on the bucket count via the call
    # signature (per-bucket shapes ride the avals); cadence and constants
    # — including the bound-pass variant — ride key_extra like the
    # homogeneous megakernel
    return aot_cache.cached_program(
        mega, "bucketed_megastep",
        key_extra=(_STATS_ROWS, settings, n_iters, bool(donate), axis,
                   # bounds-only constants keyed only when the bound-pass
                   # variant is compiled (see the homogeneous kernel);
                   # the integer-sweep ladder/scope likewise only when
                   # the sweep is compiled in
                   (float(xhat_threshold),
                    None if int_masks is None else tuple(
                        None if m is None else aot_cache.array_digest(m)
                        for m in int_masks),
                    (int_thresholds, float(rcfix_slack),
                     bool(int_rcfix),
                     None if int_cols_masks is None else tuple(
                         None if m is None else aot_cache.array_digest(m)
                         for m in int_cols_masks))
                    if int_sweep else None)
                   if bounds else None,
                   aot_cache.array_digest(nonant_idx)))


def tenant_megastep_measure_len(n_iters: int, S: int, n_tenants: int,
                                bounds: bool = False) -> int:
    """Length of the packed TENANT-BATCHED measurement
    (:func:`make_tenant_megastep`): per-tenant per-iteration stat blocks
    (``MEGA_STATS * n_iters`` each, tenant-major), per-tenant ``executed``/
    ``refresh`` scalars, the per-tenant final-iterate ``pri``/``dua``/
    ``done`` diagnostics, and — with ``bounds=True`` — ONE
    :data:`BOUND_PACK_LEN` bound pack PER TENANT (per-tenant masked
    certification; the tenant kernel never compiles the integer sweep —
    integer-sweep families are gated to solo time-slicing).

    The pack is LEAN by construction (the big-S wheel posture): x/W/xbars
    stay in the returned per-slot device states, fetched explicitly at
    join/evict/termination boundaries."""
    return n_tenants * (MEGA_STATS * n_iters + 2 + 3 * S) \
        + (n_tenants * BOUND_PACK_LEN if bounds else 0)


def tenant_megastep_unpack(vec, n_iters: int, S: int, n_tenants: int,
                           bounds: bool = False) -> dict:
    """Split a fetched :func:`make_tenant_megastep` measurement into
    PER-TENANT lists (index = slot): ``conv``/``eobj``/``pri_max``/
    ``dua_max``/``iters``/``all_done``/``narrow_sweeps``/``row_sweeps``/
    ``full_row_sweeps`` are lists of length-``n_iters`` arrays, ``executed``/``refresh_hit`` lists of scalars, ``pri``/
    ``dua``/``done`` lists of (S,) arrays; ``bounds=True`` adds
    ``bound_computed``/``bound_outer``/``bound_inner_obj``/
    ``bound_inner_feas``/``bound_sweeps`` lists (each tenant's own
    in-wheel bound pack).  Ghost/dead slots come back as inert zeros
    (``executed == 0``)."""
    vec = np.asarray(vec)
    N, T = n_iters, n_tenants
    out = {k: [] for k in _STATS_ROWS + ("executed", "refresh_hit",
                                         "pri", "dua", "done")}
    off = 0
    for _t in range(T):
        per = vec[off:off + MEGA_STATS * N].reshape(MEGA_STATS, N)
        off += MEGA_STATS * N
        for k, row in _stats_rows(per).items():
            out[k].append(row)
        out["executed"].append(int(vec[off]))
        out["refresh_hit"].append(bool(vec[off + 1]))
        off += 2
        out["pri"].append(vec[off:off + S])
        out["dua"].append(vec[off + S:off + 2 * S])
        out["done"].append(vec[off + 2 * S:off + 3 * S] != 0.0)
        off += 3 * S
    if bounds:
        for k in ("bound_computed", "bound_outer", "bound_inner_obj",
                  "bound_inner_feas", "bound_sweeps"):
            out[k] = []
        for _t in range(T):
            tail = vec[off:off + BOUND_PACK_LEN]
            off += BOUND_PACK_LEN
            out["bound_computed"].append(bool(tail[0]))
            out["bound_outer"].append(float(tail[1]))
            out["bound_inner_obj"].append(float(tail[2]))
            out["bound_inner_feas"].append(float(tail[3]))
            out["bound_sweeps"].append(float(tail[4]))
    return out


def make_tenant_megastep(nonant_idx: np.ndarray, settings: ADMMSettings,
                         n_iters: int = 8, donate: bool = True,
                         axis: str = "scen", bounds: bool = False,
                         int_nonants: np.ndarray | None = None,
                         xhat_threshold: float = 0.5):
    """ONE jitted program running up to ``n_iters`` frozen wheel
    iterations for K ISOMORPHIC TENANTS AT ONCE — the continuous-batching
    megakernel (ROADMAP item 2, doc/serving.md "Continuous batching").

    Where the bucketed kernel (:func:`make_bucketed_wheel_megastep`)
    couples its slots through a shared scenario tree, the tenant kernel
    keeps every slot a FULLY INDEPENDENT wheel: per-slot
    :class:`PHState`/:class:`PHArrays`/factors tuples (all the same
    shape family, so ONE compile serves any tenant mix of that family —
    the AOT key is effectively (family, K) via the tuple avals), and
    every reduction — xbar/W onehot contractions, the early-exit/
    acceptance masks, the in-wheel bound pack — is PER-TENANT masked:
    slot ``t``'s block solve, ``_ph_finish`` outer update, acceptance
    test ``ok_t``, convergence stop and bound pass read ONLY slot ``t``'s
    arrays.  A tenant's trajectory inside a K-batch is therefore the
    EXACT solo-megastep computation on its own state (the 1e-9 batched-
    vs-solo parity contract, pinned by tests/test_batching.py); the
    throughput win is K wheels sharing one dispatch + one host fetch per
    window instead of K park/resume/sync cycles.

    Per-slot liveness: ``live_mask[t]`` False is a GHOST SLOT — the
    slot's rows ride the program inert (dead ``lax.cond`` branch, zero
    stats, state passthrough), exactly like ghost scenarios pad an
    uneven mesh.  A finished/evicted tenant's slot goes ghost until the
    scheduler backfills it at a window boundary (join = write fresh
    state/arrays into the slot; evict = bank the slot's W/xbars/rho
    through the checkpoint seam).  ``convthresh``/``n_live``/
    ``bound_live`` are (K,) per-tenant — one tenant stopping (or
    skipping its bound cadence) never perturbs a sibling's masks.

    ``bounds=True`` appends ONE :data:`BOUND_PACK_LEN` pack PER TENANT
    (each slot's own :func:`_bound_pass_terms` under its own traced
    ``bound_live[t]`` flag) — per-tenant in-wheel certification under
    the batched source char ('B', service/batching.py).  The tenant
    kernel does NOT compile the batched integer sweep: integer-sweep
    families are gated to solo time-slicing by the scheduler (the
    sweep's global argmin semantics have no per-tenant masked form).

    Returns ``mega(states, arrs, prox_on, factors, convthresh, n_live,
    accept_tol, live_mask) -> (states, packed)`` over K-tuples of
    per-slot :class:`PHState` / :class:`PHArrays` / factors, with
    (K,)-shaped ``convthresh``/``n_live``/``live_mask``; ``bounds=True``
    adds trailing ``(bound_live, feas_tol)`` with (K,) ``bound_live``.
    Unpack with :func:`tenant_megastep_unpack`.
    """
    if n_iters < 1:
        raise ValueError(f"n_iters ({n_iters}) must be >= 1")
    idx = jnp.asarray(nonant_idx)
    int_mask = (None if int_nonants is None
                else np.asarray(int_nonants, dtype=bool))
    _, shared_frozen, _, frozen_solve = _solver_fns_for(
        settings, None, axis)

    @functools.partial(jax.jit, donate_argnums=(0,) if donate else ())
    def mega(states, arrs, prox_on, factors, convthresh, n_live,
             accept_tol, live_mask, bound_live=None, feas_tol=1e-3):
        dt = settings.jdtype()
        T = len(states)
        n_live_t = jnp.asarray(n_live, jnp.int32)
        thresh = jnp.asarray(convthresh, dt)
        tol = jnp.asarray(accept_tol, dt)
        live_m = jnp.asarray(live_mask, bool)

        def body(carry, k):
            sts, pris, duas, dones, exs, stps, rfs = carry
            new_sts, new_pris, new_duas, new_dones = [], [], [], []
            new_exs, new_stps, new_rfs, stats_rows = [], [], [], []
            for t in range(T):
                arr = arrs[t]
                fsolve = (shared_frozen if arr.A.ndim == 2
                          else frozen_solve)

                # the solo megastep's live_fn, verbatim, on slot t only —
                # per-tenant masked isolation is BY CONSTRUCTION: no
                # cross-slot array ever enters this closure
                def live_fn(op, arr=arr, fsolve=fsolve, t=t,
                            fac=factors[t]):
                    st, pri, dua, done_s, ex, stp, rf = op
                    q, q2, W, rho = _ph_objective(arr, st, prox_on, idx,
                                                  settings)
                    sol = fsolve(q, q2, arr.A, arr.cl, arr.cu, arr.lb,
                                 arr.ub, st.x, st.z, st.y, st.yx, fac)
                    ok = jnp.all(sol.done) | jnp.all(
                        (sol.pri_res <= tol) & (sol.dua_res <= tol))
                    new_st, out = _ph_finish(arr, st, sol, W, rho, idx)
                    stats = jnp.stack([
                        out.conv.astype(dt), out.eobj.astype(dt),
                        jnp.max(sol.pri_res).astype(dt),
                        jnp.max(sol.dua_res).astype(dt),
                        jnp.max(sol.iters).astype(dt),
                        jnp.all(sol.done).astype(dt),
                        *admm.width_counters(sol).astype(dt)])
                    sel = lambda a, b: jnp.where(ok, a, b)
                    new_st = jax.tree.map(sel, new_st, st)
                    return ((new_st, sel(sol.pri_res, pri),
                             sel(sol.dua_res, dua), sel(sol.done, done_s),
                             ex + ok.astype(jnp.int32),
                             stp | (ok & (out.conv < thresh[t])) | ~ok,
                             rf | ~ok),
                            stats)

                def dead_fn(op):
                    return op, jnp.zeros((MEGA_STATS,), dt)

                live_t = live_m[t] & (~stps[t]) & (k < n_live_t[t])
                (st2, pri2, dua2, done2, ex2, stp2, rf2), stats_t = \
                    jax.lax.cond(
                        live_t, live_fn, dead_fn,
                        (sts[t], pris[t], duas[t], dones[t], exs[t],
                         stps[t], rfs[t]))
                new_sts.append(st2)
                new_pris.append(pri2)
                new_duas.append(dua2)
                new_dones.append(done2)
                new_exs.append(ex2)
                new_stps.append(stp2)
                new_rfs.append(rf2)
                stats_rows.append(stats_t)
            return ((tuple(new_sts), tuple(new_pris), tuple(new_duas),
                     tuple(new_dones), tuple(new_exs), tuple(new_stps),
                     tuple(new_rfs)), jnp.stack(stats_rows))

        infs = tuple(jnp.full((arr.c.shape[0],), jnp.inf, dt)
                     for arr in arrs)
        falses = tuple(jnp.zeros((arr.c.shape[0],), bool) for arr in arrs)
        zeros_i = tuple(jnp.zeros((), jnp.int32) for _ in arrs)
        zeros_b = tuple(jnp.zeros((), bool) for _ in arrs)
        carry0 = (states, infs, infs, falses, zeros_i, zeros_b, zeros_b)
        (sts, pris, duas, dones, exs, _, rfs), stats = jax.lax.scan(
            body, carry0, jnp.arange(n_iters, dtype=jnp.int32))
        # stats is (n_iters, T, MEGA_STATS); pack tenant-major so each tenant's
        # block reads exactly like a solo measurement prefix
        parts = []
        for t in range(T):
            parts += [stats[:, t, :].T.reshape(-1),
                      exs[t].astype(dt)[None], rfs[t].astype(dt)[None],
                      pris[t].astype(dt), duas[t].astype(dt),
                      dones[t].astype(dt)]
        if bounds:
            bl = jnp.asarray(
                jnp.zeros((T,), bool) if bound_live is None else
                bound_live, bool)
            for t in range(T):
                arr = arrs[t]
                fsolve = (shared_frozen if arr.A.ndim == 2
                          else frozen_solve)

                def bounds_on(stf, arr=arr, fsolve=fsolve, t=t,
                              fac=factors[t]):
                    outer, inner, feas, sweeps = _bound_pass_terms(
                        arr, stf, idx, settings, fsolve, fac,
                        feas_tol, int_mask, xhat_threshold)
                    return jnp.stack(
                        [jnp.ones((), dt), outer, inner, feas, sweeps])

                parts.append(jax.lax.cond(
                    bl[t] & live_m[t], bounds_on,
                    lambda _: jnp.zeros((BOUND_PACK_LEN,), dt), sts[t]))
        return sts, jnp.concatenate(parts)

    # AOT key: the slot count K rides the call signature (tuple avals),
    # so the cache key is effectively (family, K) — one compile serves
    # any tenant mix of the family at that K
    return aot_cache.cached_program(
        mega, "tenant_megastep",
        key_extra=(_STATS_ROWS, settings, n_iters, bool(donate), axis,
                   (float(xhat_threshold),
                    None if int_mask is None
                    else aot_cache.array_digest(int_mask))
                   if bounds else None,
                   aot_cache.array_digest(nonant_idx)))


def collect_traces(fused, state, arr, prox_on, n_chunks: int):
    """Drive ``n_chunks`` fused dispatches, DOUBLE-BUFFERING each chunk's
    trace D2H against the next chunk's device compute.

    The serial pattern (fetch chunk k's trace, then dispatch chunk k+1)
    leaves the device idle for a full host round-trip per chunk.  Here
    chunk k+1 is dispatched
    FIRST; chunk k's trace (complete by then — the device executes in
    dispatch order) starts its host copy asynchronously and the blocking
    read happens while k+1 runs, so the fetch overlaps compute.  The
    fetches ride :func:`~tpusppy.solvers.hostsync.fetch` (explicit
    transfers, counted by open sync trackers).

    Requires a ``fused`` from :func:`make_ph_fused_step` with
    ``collect="trace"``.  Returns ``(state, trace)`` with the per-chunk
    traces concatenated on the host along the iteration axis.
    """
    from ..solvers import hostsync

    def _start_copy(tr):
        # start the D2H DMA now; the later blocking read only waits on
        # the copy, not on a cold fetch issued after the next dispatch
        jax.tree.map(lambda a: a.copy_to_host_async()
                     if hasattr(a, "copy_to_host_async") else None, tr)
        return tr

    # fetch takes the WHOLE trace pytree in one call: one counted sync
    # per chunk, matching the one round-trip it actually is
    traces = []
    prev = None
    for _ in range(max(1, int(n_chunks))):
        state, trace = fused(state, arr, prox_on)
        if prev is not None:
            traces.append(hostsync.fetch(prev, overlapped=True))
        prev = _start_copy(trace)
    traces.append(hostsync.fetch(prev))
    out = (traces[0] if len(traces) == 1 else jax.tree.map(
        lambda *xs: np.concatenate([np.asarray(x) for x in xs]), *traces))
    return state, out


def dispatch_window(mesh: Mesh) -> int:
    """How many step dispatches may be in flight before blocking.

    XLA's CPU in-process collectives have a hard 40s rendezvous timeout, and
    dozens of queued multi-device runs on an oversubscribed host starve a
    given run's all-reduce past it (observed as "Expected 8 threads to join
    ... only 7 arrived" aborts).  A small window keeps device/host overlap
    without unbounded queueing; single-device meshes have no rendezvous and
    can pipeline deep.
    """
    return 4 if len(mesh.devices.flat) > 1 else 64


def make_mesh(n_devices: int | None = None, axis: str = "scen") -> Mesh:
    devs = jax.devices()
    if n_devices is not None:
        if len(devs) < n_devices:
            raise ValueError(
                f"need {n_devices} devices, have {len(devs)}")
        devs = devs[:n_devices]
    return Mesh(np.asarray(devs), (axis,))


def make_mesh_2d(n_scen: int, n_row: int, scen_axis: str = "scen",
                 row_axis: str = "row") -> Mesh:
    """2-D mesh for the shared-A engine: scenarios x constraint ROWS.

    The row axis is the tensor-parallel analogue (SURVEY §5 "constraint-axis
    available for intra-problem sharding"): the shared (m, n) A and all
    (S, m) row-state shard over it, so huge-m families scale past one
    chip's HBM/FLOPs.  Under jit auto-partitioning the m-contractions
    (A'y, A'diag(rho)A) lower to psum over the row axis — no manual
    collectives.  Dense (per-scenario A) batches use the 1-D mesh.
    """
    devs = jax.devices()[: n_scen * n_row]
    if len(devs) < n_scen * n_row:
        raise ValueError(
            f"need {n_scen * n_row} devices, have {len(devs)}")
    return Mesh(np.asarray(devs).reshape(n_scen, n_row),
                (scen_axis, row_axis))


def shard_batch(batch, mesh: Mesh, axis: str = "scen",
                sparse: bool | str = "auto") -> PHArrays:
    """Place a :class:`~tpusppy.ir.ScenarioBatch` on the mesh, scenario-sharded.

    Pads S up to a multiple of the mesh axis size with zero-probability copies
    of scenario 0 — inert in every reduction (the batched analogue of uneven
    scenario-to-rank maps, sputils.py:807-812).  On a 2-D mesh
    (:func:`make_mesh_2d`) with a shared-A batch, the row dimension
    additionally shards over the "row" axis (m padded to a multiple of it).

    ``sparse``: upload a shared A as a :class:`~tpusppy.solvers.sparse.SparseA`
    (gather/segment-sum matvecs + block/Woodbury structured KKT when the
    family has the structure) instead of the dense (m, n) matrix.  "auto"
    enables it for large very-sparse families (reference-scale UC: 0.03%
    dense) on a 1-D mesh; dense stays the default elsewhere (small
    matrices ride the MXU better dense, and the 2-D row-sharded mesh
    needs the dense layout).
    """
    S = batch.num_scenarios
    pad = num_ghosts(S, mesh, axis)
    K = batch.tree.num_nonants
    N = batch.tree.num_nodes
    nid_sk = batch.tree.nid_sk()
    probs = batch.probs

    def padded(a):
        if pad == 0:
            return a
        return np.concatenate([a, np.repeat(a[:1], pad, axis=0)], axis=0)

    probs_p = np.concatenate([probs, np.zeros(pad)]) if pad else probs
    nid_p = padded(nid_sk)
    onehot = batch.tree.onehot_sk_n()
    if pad:
        # ghost scenarios get zero membership so they never perturb reductions
        onehot = np.concatenate([onehot, np.zeros((pad, K, N))], axis=0)

    A_shared = getattr(batch, "A_shared", None)
    # any second mesh axis (beyond the scenario axis) is the row axis —
    # make_mesh_2d's row_axis name passes through automatically
    extra = [ax for ax in mesh.axis_names if ax != axis]
    row_axis = (extra[0] if (extra and A_shared is not None) else None)

    def pad_rows(a, row_dim):
        """Pad dim ``row_dim`` to a multiple of the row-axis size (inert
        padded rows are neutralized by the caller: zero A rows with
        -inf/inf bounds)."""
        if row_axis is None:
            return a
        rsh = mesh.shape[row_axis]
        rpad = (-a.shape[row_dim]) % rsh
        if rpad == 0:
            return a
        widths = [(0, 0)] * a.ndim
        widths[row_dim] = (0, rpad)
        return np.pad(a, widths)

    if A_shared is not None:
        An = np.asarray(A_shared)
        from ..solvers.sparse import should_sparsify
        use_sparse = (sparse is True) or (
            sparse == "auto" and row_axis is None and should_sparsify(An))
        if sparse is True and row_axis is not None:
            raise ValueError(
                "sparse=True is incompatible with a 2-D row-sharded mesh: "
                "the row axis needs the dense (m, n) layout — use the 1-D "
                "mesh for the SparseA engine or sparse='auto'")
        if row_axis is not None:
            A_host = pad_rows(An, 0)
        elif use_sparse:
            A_host = SparseA.from_dense(An, structure=True)
        else:
            A_host = An
        cl_p = pad_rows(padded(batch.cl), 1)
        cu_p = pad_rows(padded(batch.cu), 1)
        m0 = batch.cl.shape[1]
        if cl_p.shape[1] != m0:
            # inert padded rows: -inf <= (zero row) x <= +inf
            cl_p[:, m0:] = -np.inf
            cu_p[:, m0:] = np.inf
    else:
        A_host = padded(batch.A)
        cl_p = padded(batch.cl)
        cu_p = padded(batch.cu)
    host = PHArrays(
        c=padded(batch.c), q2=padded(batch.q2), A=A_host,
        cl=cl_p, cu=cu_p,
        lb=padded(batch.lb), ub=padded(batch.ub),
        const=padded(batch.const), probs=probs_p,
        onehot=onehot, nid_sk=nid_p)
    # rule-driven placement: ONE declarative table maps every leaf to its
    # NamedSharding (ph_partition_rules); an unmatched leaf fails loudly
    shardings = ph_shardings(mesh, host, axis, row_axis,
                             shared=A_shared is not None)
    return jax.tree.map(
        lambda a, s: jax.device_put(jnp.asarray(a), s), host, shardings)


def init_state(arr: PHArrays, default_rho: float, settings: ADMMSettings) -> PHState:
    dt = settings.jdtype()
    S, n = arr.c.shape
    m = arr.cl.shape[1]
    K = arr.nid_sk.shape[1]
    shardS = lambda shape: jnp.zeros(shape, dt)
    state = PHState(
        W=shardS((S, K)),
        xbars=shardS((S, K)),
        rho=jnp.full((S, K), default_rho, dt),
        x=shardS((S, n)),
        z=shardS((S, m)),
        y=shardS((S, m)),
        yx=shardS((S, n)),
    )
    return jax.tree.map(jax.device_put, state, state_shardings(arr, state))


def state_shardings(arr: PHArrays, state: PHState | None = None):
    """The placement-rule shardings for a :class:`PHState` matching
    ``arr``'s mesh posture — the data shardings and the state shardings
    come from ONE table, so the first step never reshards.  Used by
    :func:`init_state` and the shard-read checkpoint restore.  Falls back
    to fully-addressable single-device placement when ``arr`` carries no
    mesh (plain jnp arrays, e.g. the host megastep path)."""
    sh = getattr(arr.c, "sharding", None)
    mesh = getattr(sh, "mesh", None)
    if state is None:
        K = arr.nid_sk.shape[1]
        S, n = arr.c.shape
        m = arr.cl.shape[1]
        z = np.zeros(())
        state = PHState(*(np.broadcast_to(z, s) for s in (
            (S, K), (S, K), (S, K), (S, n), (S, m), (S, m), (S, n))))
    if mesh is None or getattr(mesh, "empty", False):
        return jax.tree.map(lambda a: sh, state) if sh is not None else None
    axis = mesh.axis_names[0]
    extra = [ax for ax in mesh.axis_names if ax != axis]
    shared = getattr(arr.A, "ndim", 2) != 3
    row_axis = extra[0] if (extra and shared) else None
    # the row-state leaves (z, y) only shard over row_axis when cl does
    # (2-D shared-A posture) — exactly what the rules table encodes
    return ph_shardings(mesh, state, axis, row_axis, shared=shared)


def run_ph(batch, mesh: Mesh, iters: int, default_rho: float = 1.0,
           settings: ADMMSettings | None = None, axis: str = "scen",
           refresh_every: int = 32, fused: bool | str = "auto",
           chunk: int | None = None, precision: str | None = None):
    """Sharded PH driver: Iter0 (plain objective via rho=W=0 warmup step
    semantics) + ``iters`` PH iterations.  Returns (state, last PHStepOut).

    Iterations run on the factorization-amortized path: a full adaptive
    refresh at the first PH iteration and every ``refresh_every`` after it,
    sweep-only frozen steps in between (``refresh_every=1`` disables the
    frozen path).  Used by ``__graft_entry__.dryrun_multichip`` and
    ``bench.py``; the class API (:class:`tpusppy.opt.ph.PH`) remains the
    feature-complete host path.

    ``fused="auto"`` (default) packs the iterations into fused
    multi-iteration programs (:func:`make_ph_fused_step`, buffer-donated,
    same cadence hence bit-identical trajectory) whenever the shape fits
    the fused dispatch cap; segmentation-regime shapes fall back to the
    per-iteration step pair.  ``fused=False`` forces the pair path;
    ``chunk`` overrides the fused chunk size (else the cap, rounded down
    to a refresh multiple).  conv/eobj stay device-side across chunks —
    the host syncs only once per dispatch window.

    ``precision``: frozen-sweep matmul precision ("default"/"high"/
    "highest", see doc/precision.md) — shorthand for
    ``settings.sweep_precision`` so drivers can thread an autotuned mode
    without rebuilding settings.
    """
    settings = settings or ADMMSettings()
    if precision is not None:
        settings = dataclasses.replace(settings, sweep_precision=precision)
    arr = shard_batch(batch, mesh, axis)
    refresh, frozen = make_ph_step_pair(
        batch.tree.nonant_indices, settings, mesh, axis)
    state = init_state(arr, default_rho, settings)
    window = dispatch_window(mesh)
    # Iter0: W=0, prox off, cf. phbase.py:758-872
    state, out, _ = refresh(state, arr, 0.0)

    refresh_every = max(refresh_every, 1)
    cap = fused_iteration_cap(arr, settings, mesh, refresh_every)
    use_fused = iters > 0 and (
        fused is True or (fused == "auto" and cap >= refresh_every))
    if use_fused:
        if chunk is None:
            chunk = max(refresh_every,
                        (cap or iters) // refresh_every * refresh_every)
        chunk = min(chunk, iters)
        fused_cache: dict[int, object] = {}

        def fused_for(c):
            if c not in fused_cache:
                fused_cache[c] = make_ph_fused_step(
                    batch.tree.nonant_indices, settings, mesh, axis,
                    chunk=c, refresh_every=min(refresh_every, c))
            return fused_cache[c]

        done = 0
        n_call = 0
        while done < iters:
            c = min(chunk, iters - done)
            state, out = fused_for(c)(state, arr, 1.0)
            done += c
            n_call += 1
            if n_call % window == 0:
                jax.block_until_ready(out.conv)
        return state, out

    factors = None
    for i in range(iters):
        if factors is None or i % refresh_every == 0:
            state, out, factors = refresh(state, arr, 1.0)
        else:
            state, out = frozen(state, arr, 1.0, factors)
        if (i + 1) % window == 0:
            jax.block_until_ready(out.conv)
    return state, out
