"""The main path's kernels compile for the chip — asked of the TPU's own
compiler here, without the chip (on-chip-measurement §2, rehearsal 3).

Each test lowers one program at the REAL shape for a described ``v5e:2x2``
device and compiles it: what Mosaic or XLA:TPU would refuse on the chip
(unaligned blocks, scoped-VMEM overflow, i64 block indices) is refused
here, at no chip time.  Shapes are the ones ``chip_smoke.py`` runs: the
``serve`` phase's farmer (S=1000, crops_multiplier=4) for the per-scenario
sweep kernel, the elimination kernel (the polish's saddle systems and the
inverse of K) and the wheel megastep.  One
test lowers every family's frozen solve and says which sweep it holds: the
dense engine's kernel by that engine's own rule, XLA's for the shared-A
engine.

Rules this file keeps (the driver runs the suite under ``-n 6``): nothing
chip-related happens at import or collection; the topology is described
inside a module-scoped fixture, in this process, and the fixture skips when
it cannot be described; all such tests live in this ONE file (a second file
could land on another xdist worker, which cannot load libtpu again).

Mosaic refuses i64 block indices and tests/conftest.py forces x64, so the
tests arrange 32-bit themselves (``jax.enable_x64(False)``); where program
code asks ``jax.default_backend()`` (it sees the CPU here) the test steers
it, not a new option of the program.
"""

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from tpusppy.solvers import pallas_kernels as pk
from tpusppy.solvers.admm import ADMMSettings

F32 = dict(dtype="float32", eps_abs=1e-5, eps_rel=1e-5)   # README recipe
FARMER_S, FARMER_MULT = 1000, 4


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def chip32(monkeypatch):
    """32-bit jax, jax's persistent compile cache off (a described-device
    executable is written but can never be read back without a chip), and
    the program's ``jax.default_backend()`` asks answered with the device
    the test compiles for."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    with jax.enable_x64(False):
        yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


def _farmer_batch(S):
    from tpusppy.ir import ScenarioBatch
    from tpusppy.models import farmer

    return ScenarioBatch.from_problems(
        [farmer.scenario_creator(nm, num_scens=S,
                                 crops_multiplier=FARMER_MULT)
         for nm in farmer.scenario_names_creator(S)])


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def _on_chip(tree, sharding):
    """Shapes of ``tree``'s leaves, placed on the described device."""
    return jax.tree.map(lambda a: _spec(a.shape, sharding, a.dtype), tree)


def _has_mosaic_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("S", [FARMER_S, 256], ids=["full", "rung"])
def test_fused_sweeps_compiles_at_the_served_farmer_shape(S, one_chip,
                                                          chip32):
    """The sweep kernel with its checkpoint (the residual rows of the
    iterate it ends on) at the cells' batch and at the rung the loop
    narrows to."""
    b = _farmer_batch(2)
    m, n = b.num_rows, b.num_vars
    bs = pk.usable(S, m, n, platform="tpu")
    # lane-dim blocks: the whole batch or a multiple of 128 (Mosaic tiling)
    assert bs is not None and (bs == S or bs % 128 == 0)
    mat = lambda d0, d1: _spec((d0, d1, S), one_chip)
    vec = lambda d0: _spec((d0, S), one_chip)
    args = (vec(n), vec(n), mat(m, n), mat(n, m), mat(n, n), mat(n, n),
            vec(m), vec(m), vec(n), vec(n), vec(m), vec(n),
            vec(n), vec(m), vec(n), vec(m), vec(n))
    st = ADMMSettings(**F32)
    compiled = pk.fused_sweeps.lower(
        *args, n_sweeps=max(1, st.check_every), n_refine=st.solve_refine,
        sigma=float(st.sigma), alpha=float(st.alpha), bs=bs).compile()
    assert _has_mosaic_kernel(compiled)


def _while_bodies(text):
    """{body computation's name: [(opcode, result type), ...]} of every
    ``while`` in a compiled program's text, bookkeeping left out (operands
    taken apart and put together, constants, bitcasts)."""
    import re

    comps, name = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None and " = " in line:
            rhs = line.split(" = ", 1)[1]
            op = re.search(r"\)?\s([a-z\-]+)\(", rhs)
            comps[name].append((op.group(1) if op else "?",
                                rhs.split(" ", 1)[0]))
    quiet = {"parameter", "get-tuple-element", "tuple", "constant",
             "bitcast"}
    return {body: [(op, ty) for op, ty in comps[body] if op not in quiet]
            for body in re.findall(r"body=%?([\w.\-]+)", text)}


def test_frozen_solve_makes_one_kernel_call_a_step(one_chip, chip32):
    """``solve_batch_frozen`` at the cells' shape, compiled for the chip:
    both of its sweep loops (the full width of 1000 rows and the rung of
    256) hold the one ``fused_sweeps`` custom call and at most six other
    operations a step, none of them a layout change of an (S, n) or (S, m)
    state array: the loop carries the kernel's layout.  (The text at the
    commit before PR 48: 10 such copies and 6 fusions in either body.)"""
    import functools

    from tpusppy.solvers import admm

    b = _farmer_batch(2)
    S, m, n = FARMER_S, b.num_rows, b.num_vars
    st = ADMMSettings(**F32)
    assert admm.kernel_checkpoint(st, S, m, n)
    sh = lambda *shape: _spec(shape, one_chip)
    args = (sh(S, n), sh(S, n), sh(S, m, n), sh(S, m), sh(S, m), sh(S, n),
            sh(S, n))
    warm = (sh(S, n), sh(S, m), sh(S, m), sh(S, n))
    _, factors = jax.eval_shape(
        functools.partial(admm.solve_batch_factored._jitted, settings=st),
        *args)
    text = admm.solve_batch_frozen._jitted.lower(
        *args, _on_chip(factors, one_chip), settings=st,
        warm=warm).compile().as_text()
    loops = [ops for ops in _while_bodies(text).values()
             if any(op == "custom-call" for op, _ in ops)]
    assert len(loops) == 2          # the full width and the rung
    widths = set()
    for ops in loops:
        print("sweep loop body:", [op for op, _ in ops])
        (call,) = [ty for op, ty in ops if op == "custom-call"]
        widths.add(int(call.split("f32[%d," % n)[1].split("]")[0]))
        others = [(op, ty) for op, ty in ops if op != "custom-call"]
        assert len(others) <= 6, others
        state = {"f32[%d,%d]" % dims for w in (S, 256) for d in (n, m)
                 for dims in ((w, d), (d, w))}
        assert not [(op, ty) for op, ty in others if op.startswith("copy")
                    and any(shape in ty for shape in state)], others
    assert widths == {S, 256}


def test_lanes_solve_compiles_at_the_served_farmer_polish_shape(one_chip,
                                                                chip32):
    """The polish's (n+m) saddle systems of farmer S=1000 x4, one
    right-hand side: the block the selector picks fits Mosaic's VMEM."""
    b = _farmer_batch(2)
    S, N = FARMER_S, b.num_vars + b.num_rows
    bs = pk.usable_solve(S, N, 1, platform="tpu")
    assert bs == 128
    compiled = pk.lanes_solve.lower(
        _spec((N, N, S), one_chip), _spec((N, 1, S), one_chip),
        bs=bs).compile()
    assert _has_mosaic_kernel(compiled)


def test_explicit_inverse_compiles_on_the_kernel_at_farmers_K(one_chip,
                                                             chip32):
    """The dense engine's (1000, 44, 44) K, the identity on the right
    (R = N): the block the gate picks fits Mosaic's VMEM, and the lowered
    ``_explicit_inverse`` holds the kernel and no Cholesky."""
    from tpusppy.solvers import admm

    b = _farmer_batch(2)
    S, n = FARMER_S, b.num_vars
    assert pk.usable_solve(S, n, n) == 128
    lowered = jax.jit(admm._explicit_inverse).lower(_spec((S, n, n),
                                                          one_chip))
    assert "cholesky" not in lowered.as_text()
    assert _has_mosaic_kernel(lowered.compile())


@pytest.mark.parametrize("shape", [
    (1, 60, 60),        # sslp's Woodbury cap (structured_kkt.factor_lowrank)
    (1, 520, 520),      # a dense-regime shared K (shared_admm._factor_shared)
    (1, 44, 44),
    (64, 44, 44),       # lanes not filled
    (1000, 46, 46),     # past the kernel's VMEM budget
    (1000, 88, 88),     # the padded window
], ids=lambda s: "x".join(map(str, s)))
def test_explicit_inverse_declines_the_kernel(shape, one_chip, chip32):
    """What the shared-A engine and ``structured_kkt`` hand
    ``_explicit_inverse`` is a batch of 1: with the platform the chip's, it
    lowers to XLA's Cholesky path and no ``pallas_call``, as does a dense
    batch short of 128 or past n = 45."""
    from tpusppy.solvers import admm

    text = jax.jit(admm._explicit_inverse).lower(
        _spec(shape, one_chip)).as_text()
    assert "tpu_custom_call" not in text
    assert "cholesky" in text


SWEEP_FAMILIES = {
    # case: (model, creator kwargs, S).  The dense engine's two farmer
    # shapes (cm4 at S=1000 is the cells'), then every family whose A the
    # ingest finds shared (tests/test_shared_by_value.py), at the S its
    # example, its cell or chip_smoke runs.
    "farmer_cm1": ("farmer", {"crops_multiplier": 1}, 3),
    "farmer_cm4": ("farmer", {"crops_multiplier": FARMER_MULT}, FARMER_S),
    "sslp_5_15": ("sslp", {}, 3),          # the creator's default: A 20 x 85
    "sslp_10_50": ("sslp", {"num_servers": 10, "num_clients": 50}, 2000),
    "sizes": ("sizes", {}, 3),
    "netdes": ("netdes", {}, 3),
    "gbd": ("gbd", {}, 3),
    "hydro": ("hydro", {}, 9),
    "usar": ("usar", {}, 3),
    "uc_lite": ("uc_lite", {}, 100),
}


@pytest.mark.parametrize("family", sorted(SWEEP_FAMILIES))
def test_sweep_implementation_by_family(family, one_chip, chip32):
    """The frozen solve ``SPOpt._solve_amortized`` calls, lowered for the
    chip in float32 at the family's shape, with the factors as a wheel sets
    them (``factors_keep_K=False``) and as the default keeps them: a
    shared-A family's sweep is XLA's under both, the dense engine's holds
    the Pallas kernel wherever ``admm._admm_core``'s rule picks a block."""
    import functools
    import importlib

    from tpusppy.ir import ScenarioBatch
    from tpusppy.solvers import admm, shared_admm, structured_kkt

    model, kw, S = SWEEP_FAMILIES[family]
    module = importlib.import_module("tpusppy.models." + model)
    built = S if S <= 9 else 2      # shapes only: (m, n) do not depend on S
    if model == "farmer":
        kw = dict(kw, num_scens=built)
    b = ScenarioBatch.from_problems(
        [module.scenario_creator(nm, **kw)
         for nm in module.scenario_names_creator(built)])
    shared = b.A_shared is not None
    assert shared == (model != "farmer")
    m, n = b.num_rows, b.num_vars
    sh = lambda *shape: _spec(shape, one_chip)
    args = (sh(S, n), sh(S, n), sh(m, n) if shared else sh(S, m, n),
            sh(S, m), sh(S, m), sh(S, n), sh(S, n))
    warm = (sh(S, n), sh(S, m), sh(S, m), sh(S, n))
    if shared:
        factored, frozen = (shared_admm.solve_shared_factored,
                            shared_admm.solve_shared_frozen)
        expect = False
    else:
        factored, frozen = admm.solve_batch_factored, admm.solve_batch_frozen
        bs = pk.usable(S, m, n)     # the call _admm_core makes under "auto"
        expect = bs is not None and not 512 < bs < S
        if family == "farmer_cm4":
            assert expect                             # the cells' shape
    for keep_K in (False, True):
        st = ADMMSettings(factors_keep_K=keep_K, **F32)
        _, factors = jax.eval_shape(
            functools.partial(factored._jitted, settings=st), *args)
        if shared:
            # the low-rank operator comes with no K under either setting
            lowrank = structured_kkt.lowrank_kinv(args[2])
            assert lowrank == (family == "sslp_10_50")
            assert lowrank == isinstance(factors.Kinv,
                                         structured_kkt.DiagLowRank)
            assert (factors.K is not None) == (keep_K and not lowrank)
        text = frozen._jitted.lower(*args, _on_chip(factors, one_chip),
                                    settings=st, warm=warm).as_text()
        assert ("tpu_custom_call" in text) == expect, (family, keep_K)


def test_wheel_megastep_compiles_at_the_served_farmer_shape(one_chip,
                                                            chip32):
    """The hub's hot program (spopt._megastep_fn: mesh=None, donated
    state) at farmer S=1000 x4, one refresh window wide, with its sweep on
    the Pallas kernel the TPU branch picks."""
    from tpusppy.parallel import sharded

    settings = ADMMSettings(**F32)
    batch = _farmer_batch(FARMER_S)
    idx = batch.tree.nonant_indices
    arr = sharded.shard_batch(batch, sharded.make_mesh(1))
    state = sharded.init_state(arr, 1.0, settings)
    assert state.x.dtype == jnp.float32
    refresh, _ = sharded.make_ph_step_pair(idx, settings, None)
    _, _, factors = jax.eval_shape(refresh, state, arr, 1.0)
    on_chip = lambda tree: _on_chip(tree, one_chip)
    mega = sharded.make_wheel_megastep(idx, settings, None, n_iters=15,
                                       donate=True)
    compiled = mega._jitted.lower(
        on_chip(state), on_chip(arr), 1.0, on_chip(factors), 0.0, 15,
        1e-2).compile()
    mem = compiled.memory_analysis()
    print("wheel_megastep farmer S=1000 x4 memory_analysis:", mem)
    assert _has_mosaic_kernel(compiled)
    # the sweep loop narrows (admm._admm_core): the kernel once at the full
    # width of 8 blocks and once at its rung of 2 blocks of 128
    from tpusppy.solvers import admm

    assert admm._rung_width(FARMER_S, 128) == 256
    assert compiled.as_text().count("tpu_custom_call") == 2
    # one program's footprint must sit far inside one v5e chip (16 GiB)
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert 0 < total < 2 ** 32


def test_sslp_frozen_solve_compiles_with_half_the_flops_on_lowrank_factors(
        one_chip, chip32):
    """The benchmark's sslp frozen solve (S=2000, n=520, m=60, float32, no
    K in the factors, as every wheel sets it): XLA:TPU compiles it with the
    diagonal-plus-rank-60 K^-1 that ``_factor_shared`` now returns at this
    shape, and counts under half the operations of the same program handed
    the (520, 520) explicit inverse."""
    from tpusppy.solvers import shared_admm, structured_kkt

    S, m, n = 2000, 60, 520
    st = ADMMSettings(factors_keep_K=False, **F32)
    sh = lambda *shape: _spec(shape, one_chip)
    assert structured_kkt.lowrank_kinv(sh(m, n))

    def flops(Kinv):
        fac = shared_admm.SharedFactors(
            D=sh(n), E=sh(m), cost=sh(), rho_a=sh(m), rho_x=sh(n),
            gamma=sh(S), Kinv=Kinv, K=None, q2ref=sh(n))
        compiled = shared_admm.solve_shared_frozen._jitted.lower(
            sh(S, n), sh(S, n), sh(m, n), sh(S, m), sh(S, m), sh(S, n),
            sh(S, n), fac, settings=st,
            warm=(sh(S, n), sh(S, m), sh(S, m), sh(S, n))).compile()
        assert not _has_mosaic_kernel(compiled)
        return compiled.cost_analysis()["flops"]

    lowrank = flops(structured_kkt.DiagLowRank(
        dinv=sh(n), W=sh(m, n), N=sh(m, n)))
    dense = flops(sh(n, n))
    print("sslp frozen solve flops a check block: lowrank", lowrank,
          "dense", dense)
    assert 0 < lowrank < 0.5 * dense


def _sslp_adaptive_shapes(one_chip, S=2000, m=60, n=520):
    """(solve arguments, warm start) of the benchmark's sslp batch as
    shapes on the described chip."""
    sh = lambda *shape: _spec(shape, one_chip)
    return ((sh(S, n), sh(S, n), sh(m, n), sh(S, m), sh(S, m), sh(S, n),
             sh(S, n)), (sh(S, n), sh(S, m), sh(S, m), sh(S, n)))


@pytest.mark.parametrize("keep_K", [False, True])
def test_sslp_restart_piece_compiles_without_an_n_by_n_operand(
        keep_K, one_chip, chip32):
    """One restart of the benchmark's sslp adaptive solve (the piece a
    spoke takes a turn, and the scan body of the hub's refresh; S=2000,
    n=520, m=60, float32): no (520, 520) array anywhere in the compiled
    program under either ``factors_keep_K``, and under 0.6 of the
    operations XLA:TPU counted while ``_factor_shared`` built the dense K
    for the refinement (2.4097e10 a restart, under either setting, at the
    commit before: 1000 sweeps' ``while`` body counted once, so a check
    block of 4 sweeps and the factorization)."""
    import functools

    from tpusppy.solvers import shared_admm

    st = ADMMSettings(factors_keep_K=keep_K, **F32)
    args, warm = _sslp_adaptive_shapes(one_chip)
    shapes = jax.eval_shape(
        functools.partial(shared_admm._setup_program, settings=st), *args,
        warm=warm)
    compiled = shared_admm._restart_program.lower(
        *_on_chip(shapes, one_chip), settings=st).compile()
    assert "f32[520,520]" not in compiled.as_text()
    flops = compiled.cost_analysis()["flops"]
    print("sslp restart piece flops, keep_K", keep_K, flops)
    assert 0 < flops < 0.6 * 2.4097e10


@pytest.mark.parametrize("S, m, n, dense_K", [(2000, 60, 520, False),
                                              (3, 20, 85, True)],
                         ids=["sslp_10_50", "sslp_5_15"])
def test_dense_K_refine_programs_counts_the_dense_regime_only(
        S, m, n, dense_K, one_chip, chip32):
    """``shared_admm.dense_K_refine_programs`` (trace time, one a program
    whose refinement multiplies by the dense K): 0 after every program of
    sslp 10 x 50 has traced under either ``factors_keep_K`` (adaptive,
    frozen on its factors, the restart piece), above 0 for a dense-regime
    family that keeps K."""
    import functools

    from tpusppy.obs import metrics
    from tpusppy.solvers import shared_admm

    jax.clear_caches()      # a trace another test left counts nothing here
    for keep_K in (False, True):
        st = ADMMSettings(factors_keep_K=keep_K, **F32)
        args, warm = _sslp_adaptive_shapes(one_chip, S, m, n)
        _, factors = jax.eval_shape(     # traces the adaptive program
            functools.partial(shared_admm.solve_shared_factored._jitted,
                              settings=st), *args)
        shared_admm.solve_shared_frozen._jitted.lower(
            *args, _on_chip(factors, one_chip), settings=st, warm=warm)
        shapes = jax.eval_shape(
            functools.partial(shared_admm._setup_program, settings=st),
            *args, warm=warm)
        shared_admm._restart_program.lower(*_on_chip(shapes, one_chip),
                                           settings=st)
    assert metrics.value("shared_admm.adaptive_programs") > 0
    assert (metrics.value("shared_admm.dense_K_refine_programs") > 0) \
        == dense_K
