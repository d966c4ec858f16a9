"""FLOP model for the batched ADMM engines + MFU accounting.

Single source for the arithmetic-cost model that previously lived inline in
:mod:`tpusppy.solvers.segmented` (dispatch sizing) and is now also consumed
by the fused-step autotuner (:mod:`tpusppy.tune`) and the benchmark's MFU
reporting (``bench.py``/``bench_uc.py``).

The model counts the dominant matmul work only (multiply-add = 2 flops):

- one ADMM **sweep** per scenario is one (n, n) x-update apply plus an A and
  an A' matvec: ``(n^2 + 2nm) * 2`` flops, scaled by ``sparse_factor`` for
  the gather/segment-sum SparseA engine (measured 2-4x cheaper than the
  dense accounting at reference-UC shapes);
- one **factorization** is the K assembly plus the blocked inversion:
  ``(m n^2 + 3 n^3) * 2`` flops, times ``factor_batch`` (S for the dense
  per-scenario engine, 1 for the shared-A engine).

MFU is *model* flops over *nominal* peak — an accounting convention, not a
hardware counter: elementwise work, residual bookkeeping and host/dispatch
gaps all land in the denominator, so the number is conservative.  The peak
is precision-adjusted: ``matmul_precision="highest"`` on TPU runs bf16x6
passes (6 MXU passes per f32 multiply-add), so the achievable ceiling is
the bf16 peak divided by the pass count.  Report ``peak_note`` alongside
``mfu_pct`` so the assumption is auditable.
"""

from __future__ import annotations

# bf16 MXU peak per chip, matched by substring against device_kind (first
# hit wins; order matters for e.g. "v5 lite" vs "v5p").  Sources: public
# TPU spec sheets.  Unknown kinds (and CPU) have no peak: None.
_TPU_PEAKS_BF16 = (
    ("v6e", 918e12),
    ("v6", 918e12),
    ("v5p", 459e12),
    ("v5e", 197e12),
    ("v5litepod", 197e12),
    ("v5 lite", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)

# MXU passes per multiply-add at each jax matmul precision on TPU:
# "highest" = bf16x6 f32 emulation, "high" = bf16x3, "default" = plain bf16
PRECISION_PASSES = {"highest": 6, "high": 3, "default": 1}

# Conservative wall-clock speedup of a SWEEP at lowered matmul precision,
# used by dispatch sizing (segmented.dispatch_segments).  Deliberately the
# FLOOR over execution regimes, far below the theoretical pass ratios
# (6x/2x): the per-scenario dense Pallas kernel runs its contractions in
# exact f32 VPU math regardless of mode ("high" gains nothing there;
# "default" gains only the bf16-storage bandwidth saving), while the XLA
# MXU regimes gain the pass ratio.  Underestimating the speedup is the
# safe side (dispatches sized smaller than they could be); overestimating
# would let a fused program outrun its dispatch budget.  Revisit with
# measured sweep times per mode.
SWEEP_SPEEDUP = {"highest": 1.0, "high": 1.0, "default": 1.25}


def sweep_speedup(mode) -> float:
    """Dispatch-model throughput factor for a sweep at precision ``mode``
    (None = "highest" = 1.0)."""
    return SWEEP_SPEEDUP.get(mode or "highest", 1.0)


def sweep_flops(S, n, m, sparse_factor=1.0):
    """Model flops of ONE ADMM sweep over an S-scenario batch."""
    return S * (n * float(n) + 2.0 * n * m) * 2.0 * sparse_factor


def factor_flops(n, m, factor_batch=1, sparse_factor=1.0):
    """Model flops of one batch (re)factorization."""
    return factor_batch * (m * float(n) * n + 3.0 * float(n) ** 3) \
        * 2.0 * sparse_factor


def speculation_flops(S, n, m, seg_f, overlap=1, sparse_factor=1.0):
    """Worst-case model flops a PIPELINED frozen continuation may burn on
    DISCARDED speculative segments per solve (``overlap`` segments of
    ``seg_f`` sweeps each — see ``segmented.continue_frozen``).

    This is the billing term for the overlapped dispatch pipeline: the
    continuation charges its sweep budget at dispatch time, so the waste
    is bounded by exactly this amount and the total dispatched work never
    exceeds the serial worst case.  The tune stage
    (``tpusppy.tune.autotune_pipeline``) weighs it against the measured
    stop-stats RPC latency to decide whether speculation pays for a
    shape.
    """
    return max(0, int(overlap)) * max(0, int(seg_f)) \
        * sweep_flops(S, n, m, sparse_factor)


def megastep_flops(S, n, m, n_iters, sweeps, sparse_factor=1.0):
    """Model flops of ONE wheel megastep dispatch: ``n_iters`` frozen PH
    iterations (sweep work only — the refresh rides its own dispatch at
    the cadence boundary) of ``sweeps`` ADMM sweeps each.

    This is the mega-dispatch billing unit: a megastep is N iterations of
    work in one device program, so its dispatch accounting — watchdog
    sizing (``segmented.megastep_cap``), FLOP billing
    (``segmented.bill_megastep``) and the bench MFU denominator — must
    scale with N, and a watchdog- or budget-capped megastep bills only
    the iterations actually dispatched (callers pass the executed count,
    never the requested one).
    """
    return max(0, int(n_iters)) * sweep_flops(S, n, m, sparse_factor) \
        * max(float(sweeps), 1.0)


def bound_pass_flops(S, n, m, sweeps, sparse_factor=1.0, n_evals=1):
    """Model flops of ONE in-wheel bound pass (doc/pipeline.md "In-wheel
    certification"): ``n_evals`` frozen evaluations at the measured
    ``sweeps`` (1 for the legacy xhat-at-xbar pass; the batched integer
    sweep runs its C rounding candidates + 1 reduced-cost re-solve,
    doc/integer.md) plus one sweep-equivalent for the Lagrangian
    dual-objective assembly (an A'y matvec pair and per-coordinate
    closed-form minima — the same matvec volume as a single sweep)."""
    return sweep_flops(S, n, m, sparse_factor) \
        * (max(1, int(n_evals)) * max(float(sweeps), 1.0) + 1.0)


def tenant_shares(rows):
    """Live-row-fraction attribution weights for a SHARED dispatch
    (doc/serving.md "Continuous batching"): one fused tenant-batched
    megastep serves K tenants at once, and the shared wall/FLOP cost is
    split ``share_t = rows_t / sum(rows)`` where ``rows_t`` is the
    tenant's live row count weighted by the iterations it actually ran
    (``S_t * max(1, executed_t)``; 0 for ghost slots).  Returns one
    float per entry, summing to 1.0 over live tenants (all zeros ->
    all-zero shares)."""
    rows = [max(0.0, float(r)) for r in rows]
    total = sum(rows)
    if total <= 0.0:
        return [0.0] * len(rows)
    return [r / total for r in rows]


def ph_iteration_flops(S, n, m, sweeps, refresh_every=16, restarts=1,
                       factor_batch=1, sparse_factor=1.0):
    """Model flops of one PH iteration, refresh cost amortized over the
    cadence.

    ``sweeps`` is the MEASURED (or configured) ADMM sweep count per
    subproblem solve — use ``PHStepOut.iters`` from the actual run, not
    ``max_iter``, or the MFU is inflated by sweeps that never ran.  A
    refresh iteration runs ``restarts`` adaptation rounds (each a sweep
    budget + a factorization); 1 in ``refresh_every`` iterations is a
    refresh.
    """
    sw = sweep_flops(S, n, m, sparse_factor) * max(float(sweeps), 1.0)
    fa = factor_flops(n, m, factor_batch, sparse_factor)
    f = 1.0 / max(1, refresh_every)
    rst = max(1, restarts)
    return (1.0 - f) * sw + f * rst * (sw + fa)


def device_peak_flops(device=None, matmul_precision="highest"):
    """(peak_flops_per_device, note) for MFU accounting.

    (None, reason) when no peak is known: a CPU run has no device
    utilization to report, and an unknown ``device_kind`` is not guessed.
    """
    if device is None:
        import jax
        device = jax.devices()[0]
    platform = getattr(device, "platform", "cpu")
    if platform == "cpu":
        return None, "cpu: no device peak"
    kind = (getattr(device, "device_kind", "") or "").lower()
    passes = PRECISION_PASSES.get(matmul_precision, 1)
    for key, bf16 in _TPU_PEAKS_BF16:
        if key in kind:
            return bf16 / passes, (
                f"{key} {bf16/1e12:.0f}T bf16 / {passes} "
                f"({matmul_precision})")
    return None, f"unknown device_kind {kind!r}"


def mfu_pct(iters_per_sec, flops_per_iter, n_devices=1, device=None,
            matmul_precision="highest"):
    """(mfu_pct, note): model-flop utilization of the whole mesh.

    None when the peak is unknown (note says why).  ``flops_per_iter`` is
    the TOTAL model flops of one PH iteration (all scenarios), so the
    denominator scales with ``n_devices``.
    """
    peak, note = device_peak_flops(device, matmul_precision)
    if peak is None or iters_per_sec is None:
        return None, note
    achieved = iters_per_sec * flops_per_iter
    return 100.0 * achieved / (peak * max(1, n_devices)), note
