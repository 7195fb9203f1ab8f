"""Superstep executor benchmark: dispatches per iteration + fixed-cost fit.

Measures what ``GradientDescent.set_superstep`` actually changes on the
host-streamed SGD hot loop (``optimize/streamed.py``):

* **Dispatch counts** — exact, not timed: the run is instrumented
  through the repo's own failpoint hit counters
  (``optimize.streamed.step`` = compiled-program dispatches,
  ``io.device_put`` = host→device transfer events, ``io.superstep`` =
  superchunk assemblies), armed with a never-firing spec so the real
  production path is counted, not a mock.  The headline: dispatches and
  transfers per iteration drop 1/K — by construction, and verified here
  by measurement.
* **Fixed-cost/slope fit** — the GRAM_SCAN_EXPERIMENT methodology: wall
  = fixed + slope·iters least-squares over a >= 3-point iteration
  ladder per K, interleaved across repetitions with the min wall per
  point kept (ambient load only inflates walls, so the minimum is the
  least-disturbed reading).  ``slope_K1 - slope_K`` is the fitted
  per-iteration host dispatch tax the fusion recovered; it also
  calibrates ``plan.CostModel.dispatch_overhead_s``.

Headline metrics are the structural counts and the fitted slope
reduction, NOT end-to-end wall gain: this 2-core harness shares one
DRAM bandwidth wall between the host stage and the kernel, so
end-to-end ratios are ambient-state-dependent (see BENCH_INGEST.json's
honesty note; the basis string restates it).

Writes ``BENCH_SUPERSTEP.json``; env knobs: ``SUPERSTEP_ROWS``,
``SUPERSTEP_DIM``, ``SUPERSTEP_FRAC``, ``SUPERSTEP_K``,
``SUPERSTEP_REPS``.
"""

import json
import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_cpu_multi_thread_eigen=false"
).strip()

import numpy as np  # noqa: E402

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)
OUT = os.path.join(REPO, "BENCH_SUPERSTEP.json")

ROWS = int(os.environ.get("SUPERSTEP_ROWS", "100000"))
DIM = int(os.environ.get("SUPERSTEP_DIM", "32"))
FRAC = float(os.environ.get("SUPERSTEP_FRAC", "0.05"))
K = int(os.environ.get("SUPERSTEP_K", "8"))
REPS = int(os.environ.get("SUPERSTEP_REPS", "3"))
LADDER = tuple(int(x) for x in os.environ.get(
    "SUPERSTEP_LADDER", "64,128,256").split(","))


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def dataset():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(ROWS, DIM)).astype(np.float32)
    w = rng.uniform(-1, 1, DIM).astype(np.float32)
    y = (X @ w + 0.01 * rng.normal(size=ROWS)).astype(np.float32)
    return X, y


def run_wall(X, y, iters, k):
    """One full host-streamed run; returns wall seconds (the whole
    loop, steady-state: the caller warms compiles first)."""
    from tpu_sgd.config import SGDConfig
    from tpu_sgd.ops.gradients import LeastSquaresGradient
    from tpu_sgd.ops.updaters import SimpleUpdater
    from tpu_sgd.optimize.streamed import optimize_host_streamed

    cfg = SGDConfig(step_size=0.1, num_iterations=iters,
                    mini_batch_fraction=FRAC, convergence_tol=0.0,
                    sampling="indexed", seed=42)
    t0 = time.perf_counter()
    optimize_host_streamed(LeastSquaresGradient(), SimpleUpdater(), cfg,
                           X, y, np.zeros(DIM, np.float32),
                           superstep_k=k)
    return time.perf_counter() - t0


def count_dispatches(X, y, iters, k):
    """EXACT per-run dispatch/transfer counts via the production
    failpoint sites, armed with a spec that can never fire (nth=2**62)
    so hits are counted on the real path with zero behavior change."""
    from tpu_sgd.reliability import failpoints as fp
    from tpu_sgd.reliability.failpoints import fail_nth

    sites = ("optimize.streamed.step", "io.device_put", "io.superstep")
    with fp.inject_faults({s: fail_nth(2 ** 62) for s in sites}):
        run_wall(X, y, iters, k)
        return {s: fp.hits(s) for s in sites}


def fit_steady_state(points):
    """Least-squares line ``wall = fixed + slope * iters`` over >= 2
    ``(iters, wall_s)`` launches, with per-point residuals recorded.

    A two-point fit at ~0.025 ms/iter resolves tens of ms of launch
    jitter against as little slope signal (a +-25% cross-capture spread
    was measured in round 3).  A >= 3-point regression with legs long
    enough that slope signal >> jitter makes the residuals VISIBLE: the
    returned ``fit`` dict records each point, its residual, and the
    relative slope uncertainty, so the artifact shows its own error bars.

    Returns ``(slope_s_per_iter, fixed_s, fit_dict)``; a non-positive
    fitted slope falls back to the longest run's mean (fit_dict says so).
    """
    pts = sorted((int(i), float(w)) for i, w in points)
    its = np.asarray([p[0] for p in pts], np.float64)
    walls = np.asarray([p[1] for p in pts], np.float64)
    A = np.stack([np.ones_like(its), its], axis=1)
    (fixed, slope), *_ = np.linalg.lstsq(A, walls, rcond=None)
    fit = {
        "iters": [int(i) for i in its],
        "wall_s": [round(float(w), 4) for w in walls],
    }
    # record the TRUE lstsq line first (even when the fallback replaces
    # the reported numbers): the artifact must always show what was fitted
    fit["slope_fitted_ms"] = round(float(slope) * 1e3, 5)
    fit["fixed_s_fitted"] = round(float(fixed), 4)
    if slope <= 0:
        # jitter-inverted fit: report the longest run's launch-cost-
        # inclusive mean; residuals are vs that reported line, and no
        # error bar is claimed (there is no fitted slope to put one on)
        slope = walls[-1] / its[-1]
        fixed = 0.0
        fit["fallback"] = "non-positive fitted slope; longest-run mean"
    resid = walls - (fixed + slope * its)
    fit["residual_ms"] = [round(float(r) * 1e3, 2) for r in resid]
    # slope standard error (per-point jitter propagated through the fit);
    # meaningful for >= 3 genuinely fitted points
    n = len(pts)
    if n >= 3 and "fallback" not in fit:
        dof = n - 2
        s2 = float(resid @ resid) / dof
        var_slope = s2 / float(((its - its.mean()) ** 2).sum())
        fit["slope_rel_err"] = round(float(np.sqrt(var_slope)) / slope, 4)
    return float(slope), max(float(fixed), 0.0), fit


def main():
    log(f"superstep bench: {ROWS}x{DIM} f32, frac={FRAC} "
        f"({max(1, round(FRAC * ROWS))}-row batches), K=1 vs K={K}, "
        f"ladder={LADDER}, {REPS} reps")
    X, y = dataset()

    # exact dispatch accounting over one short run per driver
    n_count = LADDER[0]
    c1 = count_dispatches(X, y, n_count, 1)
    ck = count_dispatches(X, y, n_count, K)
    counts = {
        "iterations": n_count,
        "k1": c1, f"k{K}": ck,
        "per_iteration": {
            "k1_program_dispatches": round(
                c1["optimize.streamed.step"] / n_count, 4),
            f"k{K}_program_dispatches": round(
                ck["optimize.streamed.step"] / n_count, 4),
            "k1_transfers": round(c1["io.device_put"] / n_count, 4),
            f"k{K}_transfers": round(ck["io.device_put"] / n_count, 4),
        },
        "dispatch_reduction_x": round(
            c1["optimize.streamed.step"]
            / max(1, ck["optimize.streamed.step"]), 2),
        "transfer_reduction_x": round(
            c1["io.device_put"] / max(1, ck["io.device_put"]), 2),
    }
    log(f"dispatches/run at {n_count} iters: "
        f"K=1 {c1['optimize.streamed.step']} programs "
        f"+ {c1['io.device_put']} transfers; "
        f"K={K} {ck['optimize.streamed.step']} programs "
        f"+ {ck['io.device_put']} transfers")

    # warm both drivers' compiles before timing
    run_wall(X, y, 8, 1)
    run_wall(X, y, 2 * K, K)

    # interleaved ladder, min wall per (k, iters) point kept
    walls = {1: {i: [] for i in LADDER}, K: {i: [] for i in LADDER}}
    for rep in range(REPS):
        for iters in LADDER:
            for k in (1, K):
                walls[k][iters].append(run_wall(X, y, iters, k))
        log(f"rep {rep + 1}/{REPS} done")
    fits = {}
    for k in (1, K):
        pts = [(i, min(ws)) for i, ws in walls[k].items()]
        slope, fixed, fit = fit_steady_state(pts)
        fits[k] = (slope, fixed, fit)
        log(f"K={k}: slope {slope * 1e3:.3f} ms/iter, "
            f"fixed {fixed * 1e3:.0f} ms")

    slope1, fixed1, fit1 = fits[1]
    slopek, fixedk, fitk = fits[K]
    tax_recovered_ms = (slope1 - slopek) * 1e3
    # residual tax under fusion is 1/K of the full tax: scale back up
    dispatch_overhead_s = max(0.0, (slope1 - slopek) * K / (K - 1))

    result = {
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "harness": "cpu",
        "workload": {"rows": ROWS, "dim": DIM, "frac": FRAC,
                     "batch_rows": max(1, round(FRAC * ROWS)),
                     "sampling": "indexed", "k": K,
                     "ladder": list(LADDER), "reps": REPS},
        "dispatch_counts": counts,
        "k1_fit": {"slope_ms": round(slope1 * 1e3, 4),
                   "fixed_s": round(fixed1, 4), **fit1},
        f"k{K}_fit": {"slope_ms": round(slopek * 1e3, 4),
                      "fixed_s": round(fixedk, 4), **fitk},
        "fitted_dispatch_tax_recovered_ms_per_iter": round(
            tax_recovered_ms, 4),
        "implied_dispatch_overhead_s": round(dispatch_overhead_s, 6),
        "cost_model_note": (
            "plan.CostModel.dispatch_overhead_s is calibrated from "
            "implied_dispatch_overhead_s = (slope_K1 - slope_K) * "
            "K/(K-1) — the full per-iteration host dispatch tax the "
            "fusion amortizes"),
        "basis": (
            "HEADLINE = dispatch_counts (exact: production failpoint "
            "hit counters on the real path — program dispatches and "
            "host->device transfer events drop 1/K per iteration) and "
            "fitted_dispatch_tax_recovered_ms_per_iter (the slope "
            "delta of a wall = fixed + slope*iters least-squares fit "
            "over an interleaved min-wall ladder, the "
            "GRAM_SCAN_EXPERIMENT methodology).  End-to-end wall "
            "ratios are deliberately NOT headlined: this 2-core VM "
            "shares one DRAM bandwidth wall between the host sampling "
            "stage and the XLA kernel, so wall gains here are "
            "ambient-state-dependent (BENCH_INGEST.json's honesty "
            "note); the counted 1/K reduction is what carries over "
            "to a chip (what a dispatch costs there is not measured on "
            "a directly attached one)."),
    }
    with open(OUT, "w") as f:
        json.dump(result, f, indent=1)
    log(f"wrote {OUT}")
    print(json.dumps({
        "metric": "superstep_dispatch_reduction_x",
        "value": counts["dispatch_reduction_x"],
        "fitted_tax_recovered_ms_per_iter": round(tax_recovered_ms, 4),
    }))


if __name__ == "__main__":
    main()
