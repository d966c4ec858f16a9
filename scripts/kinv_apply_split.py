"""Where the shared engine's K^-1 apply pays as diagonal plus low rank, on
the chip (PERF.md section 6, PR 33; the rule is
``structured_kkt.lowrank_kinv``).

Builds sslp batches (``models/sslp.py``, the benchmark's
``sslp_10_50_2000`` settings) at a few (servers, clients) sizes either
side of the rule, S scenarios each, takes the factors of one adaptive
solve, and times ``shared_admm.solve_shared_frozen`` on them twice: handed
the (n, n) explicit inverse and handed the ``DiagLowRank`` operator, both
built here from the same scaled ``A`` and penalties whatever the rule says
for the shape.  float32 never reaches eps 1e-5 on every row, so a frozen
solve spends its whole budget (``max_iter`` sweeps): its time over its
sweeps is the time of one sweep.  Two objectives: the Lagrangian spoke's
(an LP, ``dq2 = 0``: three applies a sweep) and the hub's (prox rho 1 on the
nonants: five).  At the benchmark's own size the adaptive solve
(``solve_shared_factored``, the regime the rule picks) is timed too, and
given per sweep like the frozen one: run on two commits side by side, the
adaptive column says what the refinement's ``K x`` costs a sweep in the
rule's regime (a dense ``K`` product or two thin ones: PERF.md section 6,
PR 35), the frozen ``lowrank`` column the same for a wheel's frozen
solves.  One row whose penalty scale gamma has left 1
makes ``dq2 != 0`` and the whole batch pays two extra refinement passes a
sweep: ``factored_gamma_moved`` counts such rows after the adaptive solve,
and the prox problem's frozen solve is timed with gamma set to 1 in every
row and with one row at 1.25 as well (gamma still adapts inside a solve).

Each is timed warm, ``block_until_ready``, median of ``--reps``.

Usage (the chip): python scripts/kinv_apply_split.py [--scens 2000]
"""

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

# (servers, clients): n = servers * (clients + 2), m = servers + clients
SIZES = ((10, 50), (5, 120), (10, 120), (10, 35), (10, 25), (6, 40))


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
    ap.add_argument("--scens", type=int, default=2000)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sizes", type=int, default=len(SIZES),
                    help="how many of SIZES, from the benchmark's on")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from tpusppy.ir import ScenarioBatch
    from tpusppy.models import sslp
    from tpusppy.solvers import shared_admm, structured_kkt
    from tpusppy.solvers.admm import ADMMSettings, _explicit_inverse

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "sslp_10_50_2000.json")) as f:
        conf = json.load(f)
    S = args.scens
    # as WheelSpinner sets it for every wheel: no K in the factors
    st = ADMMSettings(factors_keep_K=False, **conf["solver_options"])
    dt = st.jdtype()
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.device_kind, "platform": dev.platform,
                      "S": S, "settings": conf["solver_options"],
                      "reps": args.reps}), flush=True)

    for servers, clients in SIZES[:args.sizes]:
        kw = dict(conf["creator_kwargs"], num_servers=servers,
                  num_clients=clients, seedoffset=args.seed)
        b = ScenarioBatch.from_problems(
            [sslp.scenario_creator(nm, **kw)
             for nm in sslp.scenario_names_creator(S)])
        A = jnp.asarray(b.A_shared, dt)
        m, n = A.shape
        rest = tuple(jnp.asarray(v, dt) for v in (b.cl, b.cu, b.lb, b.ub))
        idx = b.tree.nonant_indices
        lagr = (jnp.asarray(b.c, dt), jnp.asarray(b.q2, dt), A) + rest
        row = {"servers": servers, "clients": clients, "m": m, "n": n,
               "rule": structured_kkt.lowrank_kinv(A),
               "thin_over_dense": 2 * (-(-m // 128) * 128) / n}
        sol0, _ = shared_admm.solve_shared_factored(*lagr, settings=st)
        xbar = (b.probs[:, None] * np.asarray(sol0.x)[:, idx]).sum(0)
        q, q2 = np.array(b.c), np.array(b.q2)
        q[:, idx] -= float(conf["default_rho"]) * xbar
        q2[:, idx] += float(conf["default_rho"])
        hub = (jnp.asarray(q, dt), jnp.asarray(q2, dt), A) + rest
        for name, prob in (("lagrangian_W0", lagr), ("hub_prox", hub)):
            sol, f = shared_admm.solve_shared_factored(*prob, settings=st)
            if (servers, clients) == SIZES[0]:
                run = lambda: shared_admm.solve_shared_factored(
                    *prob, settings=st, warm=sol.raw)[0]
                ms = median_ms(run, args.reps)
                sweeps = max(int(run().iters[0]), 1)   # over all restarts
                row[f"{name}.factored_ms"] = ms
                row[f"{name}.factored_sweeps"] = sweeps
                row[f"{name}.factored_us_per_sweep"] = 1e3 * ms / sweeps
                row[f"{name}.factored_regime"] = type(f.Kinv).__name__
                row[f"{name}.factored_keeps_K"] = f.K is not None
                # rows whose penalty scale the solve moved: one is enough
                # for dq2 != 0, and so for the two extra refinement passes
                # of every sweep of the whole batch (_solve_shared_K)
                row[f"{name}.factored_gamma_moved"] = int(
                    jnp.sum(f.gamma != 1))
            with jax.default_matmul_precision(st.matmul_precision):
                As = A * f.E[:, None] * f.D[None, :]
                d = f.q2ref + f.rho_x + st.sigma
                K = jnp.einsum("mn,m,mk->nk", As, f.rho_a, As) + jnp.diag(d)
                kinvs = {
                    "dense": _explicit_inverse(K[None])[0],
                    "lowrank": structured_kkt.factor_lowrank(
                        As, d, f.rho_a)}
            xs = {}
            facs = {tag: f._replace(Kinv=Kinv, K=None)
                    for tag, Kinv in kinvs.items()}
            if (servers, clients) == SIZES[0] and name == "hub_prox":
                # the same sweep with and without its extra passes,
                # whatever the adaptive solve did to gamma
                one = jnp.ones_like(f.gamma)
                facs["lowrank_gamma_1"] = facs["lowrank"]._replace(gamma=one)
                facs["lowrank_gamma_moved"] = facs["lowrank"]._replace(
                    gamma=one.at[0].set(1.25))
            for tag, fac in facs.items():
                run = lambda fac=fac: shared_admm.solve_shared_frozen(
                    *prob, fac, settings=st, warm=sol.raw)
                ms = median_ms(run, args.reps)
                out = run()
                sweeps = max(int(out.iters[0]), 1)
                xs[tag] = np.asarray(out.x)
                row[f"{name}.{tag}_ms"] = ms
                row[f"{name}.{tag}_sweeps"] = sweeps
                row[f"{name}.{tag}_us_per_sweep"] = 1e3 * ms / sweeps
                row[f"{name}.{tag}_worst_res"] = float(
                    max(jnp.max(out.pri_res), jnp.max(out.dua_res)))
            row[f"{name}.dense_over_lowrank"] = (
                row[f"{name}.dense_us_per_sweep"]
                / row[f"{name}.lowrank_us_per_sweep"])
            row[f"{name}.max_dx"] = float(
                np.max(np.abs(xs["dense"] - xs["lowrank"])))
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
