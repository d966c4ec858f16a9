"""Where the refresh solve's time goes, on the chip (PERF.md section 5).

Builds the batch of the benchmark's ``farmer_cm4_s1000`` cells (farmer,
``crops_multiplier 4``, S=1000, the configuration's ``solver_options``) and
times ``admm.solve_batch_factored``, the program every refresh of every
cylinder runs, under two objectives: the Lagrangian spoke's (W = 0: the
plain LP) and the hub's prox objective (rho 1 around the LP solutions'
mean).  No switch exists for this in the source: the split comes from the
settings the program already has.

  full        as the cell runs it
  no polish   ``polish=False``: full - this = the polish
  1 restart   ``restarts=1`` (polish on): (full - this) / 3 = one restart
              = one factorization + its sweeps
  frozen      ``solve_batch_frozen`` on the returned factors: sweeps alone

Each is timed warm, ``block_until_ready``, median of ``--reps`` (20), cold
(no warm start, the program's full sweep budget) and warm-started from its
own raw iterate (few sweeps: what is not sweeps).  The dense pieces are
also timed alone: ``jnp.linalg.solve`` on the polish's (S, n+m, n+m) saddle
system and the Cholesky inverse of the (S, n, n) K (the last restart's),
``pallas_kernels.lanes_solve`` on both where it applies, ``max |K Kinv - I|``
of either inverse, and ``admm._explicit_inverse`` as the program calls it.

Usage (the chip): python scripts/refresh_split.py [--scens 1000] [--reps 20]
"""

import argparse
import dataclasses
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def median_ms(fn, reps):
    import jax

    jax.block_until_ready(fn())
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        out.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(out)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scens", type=int, default=1000)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from tpusppy.ir import ScenarioBatch
    from tpusppy.models import farmer
    from tpusppy.solvers import admm, pallas_kernels
    from tpusppy.solvers.admm import ADMMSettings

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "farmer_cm4_s1000.json")) as f:
        conf = json.load(f)
    S = args.scens
    kw = dict(conf["creator_kwargs"], num_scens=S, seedoffset=args.seed)
    b = ScenarioBatch.from_problems(
        [farmer.scenario_creator(nm, **kw)
         for nm in farmer.scenario_names_creator(S)])
    st = ADMMSettings(**conf["solver_options"])
    dt = st.jdtype()
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.device_kind, "platform": dev.platform,
                      "S": S, "n": b.num_vars, "m": b.num_rows,
                      "settings": conf["solver_options"],
                      "reps": args.reps}), flush=True)

    A, cl, cu, lb, ub = (jnp.asarray(v, dt)
                         for v in (b.A, b.cl, b.cu, b.lb, b.ub))
    idx = b.tree.nonant_indices
    rho = float(conf["default_rho"])

    def problem(q, q2):
        return (jnp.asarray(q, dt), jnp.asarray(q2, dt), A, cl, cu, lb, ub)

    lagr = problem(b.c, b.q2)
    x0 = np.asarray(admm.solve_batch_factored(*lagr, settings=st)[0].x)
    xbar = (b.probs[:, None] * x0[:, idx]).sum(0)
    q, q2 = np.array(b.c), np.array(b.q2)
    q[:, idx] -= rho * xbar
    q2[:, idx] += rho
    for name, prob in (("lagrangian_W0", lagr), ("hub_prox", problem(q, q2))):
        sol, factors = admm.solve_batch_factored(*prob, settings=st)
        variants = {
            "full": st,
            "no_polish": dataclasses.replace(st, polish=False),
            "one_restart": dataclasses.replace(st, restarts=1),
        }
        for start, warm in (("cold", None), ("warm", sol.raw)):
            row = {}
            for tag, s in variants.items():
                run = lambda s=s: admm.solve_batch_factored(
                    *prob, settings=s, warm=warm)[0]
                row[tag + "_ms"] = median_ms(run, args.reps)
                row[tag + "_iters"] = int(run().iters[0])
            run = lambda: admm.solve_batch_frozen(
                *prob, factors, settings=st, warm=warm)
            row["frozen_ms"] = median_ms(run, args.reps)
            row["frozen_iters"] = int(run().iters[0])
            row["frozen_all_done"] = bool(jnp.all(run().done))
            # one restart = a factorization and its sweeps; the sweeps of
            # the whole program at the frozen solve's price per sweep
            per_sweep = row["frozen_ms"] / max(row["frozen_iters"], 1)
            row["polish_ms"] = row["full_ms"] - row["no_polish_ms"]
            row["sweeps_ms"] = per_sweep * row["full_iters"]
            row["factor_ms"] = max(
                0.0, (row["full_ms"] - row["one_restart_ms"])
                - per_sweep * (row["full_iters"] - row["one_restart_iters"])
            ) * st.restarts / max(st.restarts - 1, 1)
            row["rest_ms"] = (row["full_ms"] - row["polish_ms"]
                              - row["sweeps_ms"] - row["factor_ms"])
            print(json.dumps({f"{name}.{start}": row}), flush=True)

    # the dense pieces alone, on matrices of the program's own making
    n, m = b.num_vars, b.num_rows
    N = n + m
    _, factors = admm.solve_batch_factored(*lagr, settings=st)
    K = factors.K
    # the polish's saddle system at the LP solutions' own active sets
    xs = jnp.asarray(x0, dt)
    Ax = jnp.einsum("smn,sn->sm", A, xs)
    tol = 1e-4 * (1.0 + jnp.abs(Ax))
    ra = ((jnp.abs(Ax - cl) < tol) | (jnp.abs(Ax - cu) < tol))[:, :, None]
    va = ((xs - lb < 1e-4) | (ub - xs < 1e-4))[:, :, None]
    eye_n, eye_m = jnp.eye(n, dtype=dt), jnp.eye(m, dtype=dt)
    M = jnp.concatenate([
        jnp.concatenate([jnp.where(va, eye_n, 1e-6 * eye_n),
                         jnp.where(va, 0.0, jnp.swapaxes(A, 1, 2))], axis=2),
        jnp.concatenate([jnp.where(ra, A, 0.0),
                         jnp.where(ra, -1e-6 * eye_m, eye_m)], axis=2)],
        axis=1)
    rhs = jnp.concatenate([jnp.where(va[..., 0], xs, -lagr[0]),
                           jnp.where(ra[..., 0], Ax, 0.0)], axis=1)
    pieces = {}
    with jax.default_matmul_precision(st.matmul_precision):
        solve = jax.jit(lambda M, r: jnp.linalg.solve(M, r[..., None])[..., 0])
        # XLA's Cholesky path by name: ``_explicit_inverse`` itself takes
        # the kernel at this shape since PR 43
        inv = jax.jit(admm._explicit_inverse_oneshot)
        pieces[f"xla_lu_solve_{N}_ms"] = median_ms(
            lambda: solve(M, rhs), args.reps)
        pieces[f"xla_chol_inverse_{n}_ms"] = median_ms(
            lambda: inv(K), args.reps)
        for tag, mat, r in (
                (f"lanes_solve_{N}x1", M, rhs[..., None]),
                (f"lanes_inverse_{n}", K,
                 jnp.broadcast_to(jnp.eye(n, dtype=dt), K.shape))):
            bs = pallas_kernels.usable_solve(S, mat.shape[1], r.shape[2],
                                             dtype=dt)
            if bs is None:
                pieces[tag + "_ms"] = None
                continue
            lanes = jax.jit(lambda mat, r, bs=bs: jnp.transpose(
                pallas_kernels.lanes_solve(
                    jnp.transpose(mat, (1, 2, 0)),
                    jnp.transpose(r, (1, 2, 0)), bs=bs), (2, 0, 1)))
            pieces[tag + "_ms"] = median_ms(lambda: lanes(mat, r), args.reps)
            ref = (solve(mat, r[..., 0])[..., None] if r.shape[2] == 1
                   else inv(mat))
            got = lanes(mat, r)
            pieces[tag + "_vs_xla_rel"] = float(
                jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref)))
            if r.shape[2] > 1:
                # max |K Kinv - I| of either inverse, the product in float64
                # on the host
                K64 = np.asarray(mat, np.float64)
                for side, Kinv in (("xla", ref), ("lanes", got)):
                    pieces[f"{tag}_{side}_residual"] = float(np.abs(
                        K64 @ np.asarray(Kinv, np.float64) - np.eye(n)).max())
        # the inverse as the program takes it (transposes included)
        prog = jax.jit(lambda K: admm._explicit_inverse(K, st))
        pieces[f"program_inverse_{n}_ms"] = median_ms(lambda: prog(K),
                                                      args.reps)
        pieces[f"program_inverse_{n}_on_kernel"] = admm.lanes_inverse(
            st, S, m, n)
    print(json.dumps({"pieces": pieces}), flush=True)


if __name__ == "__main__":
    main()
