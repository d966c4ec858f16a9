"""Sparse shared-A matvecs + block/Woodbury structured KKT
(tpusppy/solvers/sparse.py, structured_kkt.py) — parity against the dense
shared engine, and the sharded PH step running on a SparseA."""

import numpy as np
import jax.numpy as jnp
import pytest

from tpusppy.solvers import admm, shared_admm
from tpusppy.solvers.sparse import SparseA, detect_structure
from tpusppy.solvers import structured_kkt as sk


def _block_lp(seed=42, n_blk=6, bs=5, S=5):
    rng = np.random.default_rng(seed)
    n = n_blk * bs
    rows = []
    for k in range(n_blk):
        for _ in range(7):
            r = np.zeros(n)
            idx = rng.choice(np.arange(k * bs, (k + 1) * bs), 3,
                             replace=False)
            r[idx] = rng.normal(size=3)
            rows.append(r)
    for _ in range(3):
        rows.append(np.where(rng.random(n) < 0.6, rng.normal(size=n), 0.0))
    A = np.array(rows)
    b = rng.normal(size=(S, n)) @ A.T
    c = rng.normal(size=(S, n))
    return A, c, b - 1.0, b + 1.0, np.full((S, n), -10.0), np.full((S, n), 10.0)


def test_sparse_matvec_ops():
    rng = np.random.default_rng(0)
    m, n, S = 40, 30, 5
    A = np.where(rng.random((m, n)) < 0.1, rng.normal(size=(m, n)), 0.0)
    sp = SparseA.from_dense(A, jnp.float64)
    x = rng.normal(size=(S, n))
    y = rng.normal(size=(S, m))
    assert np.allclose(np.asarray(sp.matvec(jnp.asarray(x))), x @ A.T)
    assert np.allclose(np.asarray(sp.rmatvec(jnp.asarray(y))), y @ A)
    assert np.allclose(np.asarray(sp.todense()), A)
    E = rng.random(m) + 0.5
    D = rng.random(n) + 0.5
    assert np.allclose(
        np.asarray(sp.scale(jnp.asarray(E), jnp.asarray(D)).todense()),
        E[:, None] * A * D[None, :])
    # empty rows/cols (all-zero row) must give 0, not -inf
    A2 = A.copy()
    A2[3, :] = 0.0
    sp2 = SparseA.from_dense(A2)
    assert float(np.asarray(sp2.row_absmax())[3]) == 0.0


def test_sparsea_pytree_keeps_its_children_under_scale_and_astype():
    """COO triplets, the CSC permutation and the attached structure: the
    one form of the matrix.  ``scale`` and ``astype`` touch the values only,
    so every program traced on one accepts the others."""
    import jax

    A, *_ = _block_lp()
    m, n = A.shape
    rng = np.random.default_rng(2)
    E, D = rng.random(m) + 0.5, rng.random(n) + 0.5
    sp = SparseA.from_dense(A, jnp.float64, structure=True, min_blocks=2)
    assert sp.structure is not None
    children, shape = sp.tree_flatten()
    assert len(children) == 5 and shape == (m, n)
    scaled = sp.scale(jnp.asarray(E), jnp.asarray(D))
    low = sp.astype(jnp.float32)
    treedef = jax.tree_util.tree_structure(sp)
    for other in (scaled, low, SparseA.tree_unflatten(shape, children)):
        assert jax.tree_util.tree_structure(other) == treedef
        assert other.rows is sp.rows and other.cols is sp.cols
        assert other.perm_csc is sp.perm_csc
        assert other.structure is sp.structure
    assert low.dtype == jnp.float32 and scaled.dtype == jnp.float64
    assert np.allclose(np.asarray(scaled.todense()),
                       E[:, None] * A * D[None, :])
    assert np.allclose(np.asarray(low.todense()), A, atol=1e-6)


def test_structured_kinv_parity():
    A, *_ = _block_lp()
    rng = np.random.default_rng(1)
    m, n = A.shape
    st = detect_structure(A, min_blocks=2)
    assert st is not None and st.r == 3
    sa = SparseA.from_dense(A, jnp.float64)
    struct = sk.StructureArrays.from_structure(st)
    d = rng.random(n) + 0.5
    rho = rng.random(m) + 0.5
    bw = sk.factor_structured(sa, struct, jnp.asarray(d),
                              jnp.asarray(rho), 1e-6)
    K = np.diag(d + 1e-6) + A.T @ (rho[:, None] * A)
    b = rng.normal(size=(4, n))
    x_ref = np.linalg.solve(K, b.T).T
    x = np.asarray(sk.kinv_apply(bw, jnp.asarray(b)))
    assert np.abs(x - x_ref).max() / np.abs(x_ref).max() < 1e-10


@pytest.mark.parametrize("q2v", [0.0, 1.0])
@pytest.mark.parametrize("structured", [False, True])
def test_shared_engine_sparse_parity(q2v, structured):
    A, c, cl, cu, lb, ub = _block_lp()
    S, n = c.shape
    q2 = np.full((S, n), q2v)
    st = admm.ADMMSettings(max_iter=2000, restarts=3, polish=False)
    sol_d = shared_admm.solve_shared(c, q2, jnp.asarray(A), cl, cu, lb, ub,
                                     settings=st)
    sp = SparseA.from_dense(A, jnp.float64, structure=structured,
                            min_blocks=2)
    assert (sp.structure is not None) == structured
    sol_s = shared_admm.solve_shared(c, q2, sp, cl, cu, lb, ub, settings=st)

    def obj(sol):
        x = np.asarray(sol.x)
        return (np.einsum("sn,sn->s", c, x)
                + 0.5 * np.einsum("sn,sn->s", q2, x * x))

    rel = np.abs(obj(sol_s) - obj(sol_d)).max() / max(
        1.0, np.abs(obj(sol_d)).max())
    assert rel < 1e-8


def test_sharded_ph_step_sparse_parity():
    """The sharded PH refresh/frozen pair on a SparseA batch matches the
    dense upload on the UC-lite family (virtual mesh of all local
    devices)."""
    from tpusppy.ir import ScenarioBatch
    from tpusppy.models import uc_lite
    from tpusppy.parallel import sharded

    S = 8
    names = uc_lite.scenario_names_creator(S)
    kw = {"num_gens": 4, "horizon": 6, "num_scens": S,
          "relax_integers": True}
    batch = ScenarioBatch.from_problems(
        [uc_lite.scenario_creator(nm, **kw) for nm in names])
    assert batch.A_shared is not None
    settings = admm.ADMMSettings(max_iter=400, restarts=2, polish_passes=1)
    mesh = sharded.make_mesh()

    def run(sparse):
        arr = sharded.shard_batch(batch, mesh, sparse=sparse)
        refresh, frozen = sharded.make_ph_step_pair(
            batch.tree.nonant_indices, settings, mesh)
        state = sharded.init_state(arr, 1.0, settings)
        state, out, _ = refresh(state, arr, 0.0)
        state, out, factors = refresh(state, arr, 1.0)
        state, out = frozen(state, arr, 1.0, factors)
        return float(np.asarray(out.eobj)), float(np.asarray(out.conv))

    eobj_d, conv_d = run(False)
    eobj_s, conv_s = run(True)
    assert abs(eobj_s - eobj_d) / max(1.0, abs(eobj_d)) < 1e-6
    assert abs(conv_s - conv_d) < 1e-6 * max(1.0, abs(conv_d))


def test_structure_detection_uc_lite():
    """A 12-gen fleet has wide balance/reserve rows (>8 nnz), so the
    block/Woodbury split must be found; at 4 gens those rows fall under
    the narrow threshold and merge everything into one component —
    detection correctly returns None there (covered implicitly by the
    parity tests running unstructured)."""
    from tpusppy.ir import ScenarioBatch
    from tpusppy.models import uc_lite

    S = 2
    names = uc_lite.scenario_names_creator(S)
    kw = {"num_gens": 12, "horizon": 8, "num_scens": S,
          "relax_integers": True}
    batch = ScenarioBatch.from_problems(
        [uc_lite.scenario_creator(nm, **kw) for nm in names])
    st = detect_structure(batch.A_shared, min_blocks=2)
    assert st is not None
    assert st.r > 0
    # blocks partition the variables exactly once
    seen = np.concatenate([bv[bv < st.n].ravel() for bv, _ in st.buckets])
    assert sorted(seen.tolist()) == list(range(st.n))


def test_spopt_wheel_path_sparse_parity():
    """The host PH path (SPOpt solve_loop + Edualbound certified bounds)
    produces the same trajectory and dual bound with sparse_device_A
    forced on as with the dense upload (uc_lite family)."""
    from tpusppy.models import uc_lite
    from tpusppy.phbase import PHBase  # noqa: F401

    S = 6
    names = uc_lite.scenario_names_creator(S)
    kw = {"num_gens": 4, "horizon": 6, "num_scens": S,
          "relax_integers": True}

    def run(sparse_opt):
        opts = {"defaultPHrho": 2.0, "PHIterLimit": 4, "convthresh": -1.0,
                "sparse_device_A": sparse_opt,
                "solver_options": {"max_iter": 400, "restarts": 2}}
        ph = PHBase(opts, names, uc_lite.scenario_creator,
                    scenario_creator_kwargs=kw)
        ph.Iter0()
        ph.iterk_loop()
        bound = ph.Edualbound()
        return ph.Eobjective(), bound

    eobj_d, bound_d = run(False)
    eobj_s, bound_s = run(True)
    assert abs(eobj_s - eobj_d) / max(1.0, abs(eobj_d)) < 1e-6
    assert abs(bound_s - bound_d) / max(1.0, abs(bound_d)) < 1e-6


def test_structure_redetect_after_cut_augmentation():
    """Cross-scenario cut rounds append DENSE rows to the shared A
    (extensions/cross_scen_extension.py): the sparse upload must rebuild
    with the cut rows classified as wide coupling rows and keep solving
    in parity with the dense engine."""
    A, c, cl, cu, lb, ub = _block_lp()
    S, n = c.shape
    rng = np.random.default_rng(7)
    # augment: 3 dense eta-style cut rows, loose bounds
    cuts = rng.normal(size=(3, n))
    A2 = np.vstack([A, cuts])
    cl2 = np.hstack([cl, np.full((S, 3), -1e3)])
    cu2 = np.hstack([cu, np.full((S, 3), 1e3)])
    q2 = np.zeros((S, n))
    st = admm.ADMMSettings(max_iter=2000, restarts=3, polish=False)

    sp = SparseA.from_dense(A2, jnp.float64, structure=True, min_blocks=2)
    assert sp.structure is not None
    # all 6 original wide + 3 cut rows must be coupling rows
    assert sp.structure.wide_rows.shape[0] == 3 + 3
    sol_s = shared_admm.solve_shared(c, q2, sp, cl2, cu2, lb, ub,
                                     settings=st)
    sol_d = shared_admm.solve_shared(c, q2, jnp.asarray(A2), cl2, cu2,
                                     lb, ub, settings=st)

    def obj(sol):
        return np.einsum("sn,sn->s", c, np.asarray(sol.x))

    rel = np.abs(obj(sol_s) - obj(sol_d)).max() / max(
        1.0, np.abs(obj(sol_d)).max())
    assert rel < 1e-8
