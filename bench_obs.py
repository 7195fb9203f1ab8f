"""Observability overhead benchmark: what tracing+counters cost on the
streamed-superstep and device-resident hot paths.

The claim under test (ISSUE 8 acceptance): with the full production
observability config ON — span tracing to a real ``JsonLinesEventLog``
plus the runtime counter patches — the warmed hot paths show **ZERO
additional dispatches, compiles, or host syncs** versus disabled.  The
disabled baseline is measured by the ``tpu_sgd.analysis`` runtime twins
(``count_dispatches`` / ``count_host_syncs``); the enabled run is
measured by the promoted counters themselves (``tpu_sgd.obs.counters``
— the twins' machinery running as the production accounting layer), and
the numbers must agree exactly.  Any nonzero delta fails the bench
loudly.

Headline metrics are the **count deltas** (and the measured
disabled-hook cost in nanoseconds), NOT wall-clock: this 2-core harness
shares one DRAM wall between host and kernel and drowns millisecond
timing in ambient noise (ROADMAP harness policy; the
BENCH_SUPERSTEP.json basis note).  Wall-clock deltas are reported as
SECONDARY with explicit basis strings: the enabled config's wall
overhead is real but structural — counting launches requires declining
jit's C++ fastpath, so every dispatch takes the Python path — and is
the price of the accounting, not of the span machinery (spans alone,
counters off, ride the same dispatch path as disabled).

Writes ``BENCH_OBS.json``; env knobs: ``OBS_ROWS``, ``OBS_DIM``,
``OBS_ITERS``, ``OBS_K``, ``OBS_C``, ``OBS_REPS``.
"""

import json
import os
import statistics
import sys
import tempfile
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_cpu_multi_thread_eigen=false"
).strip()

import numpy as np  # noqa: E402

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)
OUT = os.path.join(REPO, "BENCH_OBS.json")

ROWS = int(os.environ.get("OBS_ROWS", "20000"))
DIM = int(os.environ.get("OBS_DIM", "32"))
ITERS = int(os.environ.get("OBS_ITERS", "640"))
K = int(os.environ.get("OBS_K", "8"))
C = int(os.environ.get("OBS_C", "16"))
REPS = int(os.environ.get("OBS_REPS", "5"))


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def dataset():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(ROWS, DIM)).astype(np.float32)
    w = rng.uniform(-1, 1, DIM).astype(np.float32)
    y = (X @ w + 0.01 * rng.normal(size=ROWS)).astype(np.float32)
    return X, y


def run_stream(X, y, k, c):
    """One full-batch host-streamed run on the REAL driver stack;
    returns (weights, wall seconds)."""
    from tpu_sgd.config import SGDConfig
    from tpu_sgd.ops.gradients import LeastSquaresGradient
    from tpu_sgd.ops.updaters import SimpleUpdater
    from tpu_sgd.optimize.streamed import optimize_host_streamed

    cfg = SGDConfig(step_size=0.01, num_iterations=ITERS,
                    mini_batch_fraction=1.0, convergence_tol=0.0,
                    sampling="bernoulli", seed=42)
    t0 = time.perf_counter()
    w, _ = optimize_host_streamed(
        LeastSquaresGradient(), SimpleUpdater(), cfg, X, y,
        np.zeros(DIM, np.float32), superstep_k=k, resident_cadence=c)
    dt = time.perf_counter() - t0
    return np.asarray(w), dt


def measure_path(name, X, y, k, c, trace_dir):
    """Counts + walls for one hot path, obs OFF then obs ON."""
    from tpu_sgd import obs
    from tpu_sgd.analysis.runtime import count_dispatches, count_host_syncs
    from tpu_sgd.obs import counters as obs_counters
    from tpu_sgd.utils.events import JsonLinesEventLog

    log(f"[{name}] warm + disabled baseline ...")
    w_warm, _ = run_stream(X, y, k, c)  # compile everything
    # the disabled compile baseline rides the same jax.monitoring
    # funnel the enabled counters listen on (NOT zero: the streamed
    # driver backend-compiles one small per-run program even warmed —
    # a pre-existing cost the delta must not blame on obs)
    from jax._src import monitoring as _monitoring

    compiles_off = [0]

    def _listener(ev_name, dur, **kw):
        if ev_name.endswith("backend_compile_duration"):
            compiles_off[0] += 1

    _monitoring.register_event_duration_secs_listener(_listener)
    try:
        with count_host_syncs() as sc, count_dispatches() as dc:
            w_off, _ = run_stream(X, y, k, c)
    finally:
        _monitoring.unregister_event_duration_listener(
            _listener)
    off = {"dispatches": dc["n"], "host_syncs": sc["n"],
           "compiles": compiles_off[0]}
    np.testing.assert_array_equal(w_off, w_warm)
    walls_off = [run_stream(X, y, k, c)[1] for _ in range(REPS)]

    log(f"[{name}] enabled (tracing -> JSONL + counters) ...")
    trace = os.path.join(trace_dir, f"{name}.jsonl")
    obs.enable(trace)
    try:
        # enable() drops the C++ fastpath cache entries; one run
        # re-traces them (no XLA recompile — asserted below) so the
        # counted/timed runs compare steady state to steady state
        run_stream(X, y, k, c)
        obs_counters.reset()
        w_on, _ = run_stream(X, y, k, c)
        snap = obs_counters.snapshot()
        walls_on = [run_stream(X, y, k, c)[1] for _ in range(REPS)]
    finally:
        obs.disable()
    np.testing.assert_array_equal(w_on, w_warm)
    spans = sum(1 for r in JsonLinesEventLog.read(trace)
                if r.get("kind") == "trace_span")

    def total(kind):
        return sum(v["n"] for key, v in snap.items()
                   if key.endswith("." + kind))

    on = {"dispatches": total("dispatch"),
          "host_syncs": total("host_sync"),
          "compiles": total("compile")}
    deltas = {k: on[k] - off[k] for k in on}
    # THE acceptance gate: observability must be structurally free
    assert deltas == {"dispatches": 0, "host_syncs": 0, "compiles": 0}, (
        f"{name}: enabled obs changed the runtime-event counts: {deltas} "
        f"(off={off}, on={on})")
    log(f"[{name}] deltas all ZERO (off={off}); "
        f"{spans} spans emitted per run")
    return {
        "counts_disabled": off,
        "counts_enabled": on,
        "count_deltas_enabled_minus_disabled": deltas,
        # the trace holds REPS+2 runs: the post-enable re-warm, the
        # counted run, and the REPS timed runs
        "trace_spans_per_run": spans // (REPS + 2),
        "wall_s_disabled": [round(t, 5) for t in walls_off],
        "wall_s_enabled": [round(t, 5) for t in walls_on],
        "wall_median_disabled_s": round(statistics.median(walls_off), 5),
        "wall_median_enabled_s": round(statistics.median(walls_on), 5),
        "wall_overhead_per_iter_us": round(
            (statistics.median(walls_on) - statistics.median(walls_off))
            / ITERS * 1e6, 2),
    }


def disabled_hook_cost_ns():
    """The measured no-op: ns per disabled span()/event()/inc() call."""
    from tpu_sgd.obs import counters as obs_counters
    from tpu_sgd.obs import spans as obs_spans

    n = 500_000
    out = {}
    for label, fn in (
            ("span", lambda: obs_spans.span("train.step")),
            ("event", lambda: obs_spans.event("reliability.retry")),
            ("inc", lambda: obs_counters.inc("train.io_callback"))):
        t0 = time.perf_counter()
        for _ in range(n):
            fn()
        out[label] = round((time.perf_counter() - t0) / n * 1e9, 1)
    return out


def main():
    log(f"obs bench: {ROWS}x{DIM} f32 full batch, {ITERS} iters, "
        f"K={K}, C={C}, reps={REPS}")
    X, y = dataset()
    hooks_ns = disabled_hook_cost_ns()
    log(f"disabled hook cost: {hooks_ns} ns/call")
    with tempfile.TemporaryDirectory() as trace_dir:
        superstep = measure_path("superstep", X, y, K, 0, trace_dir)
        resident = measure_path("resident", X, y, K, C, trace_dir)

    doc = {
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "harness": "cpu",
        "workload": {"rows": ROWS, "dim": DIM, "iters": ITERS,
                     "full_batch": True, "k": K, "cadence": C,
                     "reps": REPS},
        "headline": {
            "basis": (
                "count deltas (enabled minus disabled) measured by the "
                "analysis runtime twins (disabled) and the promoted "
                "obs.counters (enabled) on warmed drivers; counts are "
                "exact and noise-immune — the 2-core harness policy. "
                "Disabled hook cost is the per-call price every "
                "production process pays when nobody opts in."),
            "superstep_count_deltas":
                superstep["count_deltas_enabled_minus_disabled"],
            "resident_count_deltas":
                resident["count_deltas_enabled_minus_disabled"],
            "disabled_hook_cost_ns_per_call": hooks_ns,
        },
        "secondary_wall_clock": {
            "basis": (
                "median of REPS end-to-end runs, quiet-as-available "
                "2-core CPU host; enabled overhead is dominated by "
                "declining jit's C++ fastpath so dispatches stay "
                "countable (structural, not span cost) plus one JSONL "
                "record write per span; treat as indicative only — "
                "ambient DRAM-wall noise on this harness is the same "
                "order (ROADMAP harness policy; BENCH_SUPERSTEP.json "
                "basis note)"),
            "superstep": {k: superstep[k] for k in (
                "wall_s_disabled", "wall_s_enabled",
                "wall_median_disabled_s", "wall_median_enabled_s",
                "wall_overhead_per_iter_us")},
            "resident": {k: resident[k] for k in (
                "wall_s_disabled", "wall_s_enabled",
                "wall_median_disabled_s", "wall_median_enabled_s",
                "wall_overhead_per_iter_us")},
        },
        "detail": {"superstep": superstep, "resident": resident},
    }
    with open(OUT, "w") as f:
        json.dump(doc, f, indent=1)
    log(f"wrote {OUT}")
    print(json.dumps(doc["headline"], indent=2))
    return 0


if __name__ == "__main__":
    sys.exit(main())
