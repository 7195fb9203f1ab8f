#!/usr/bin/env python3
"""Drive the trainer once on the chip, through the entry points a user calls.

    python chip_smoke.py              # one TPU chip: every phase below
    python chip_smoke.py --chips 4    # four chips: data-parallel phase only

One process, no children; it never sets ``JAX_PLATFORMS``.  ``jax.devices()``
is read once: anything but a TPU prints a failing last line and exits
non-zero.  A phase that raises ends the run non-zero — nothing here catches
an error to carry on.

Every phase prints one JSON line (name, shapes, seconds for the first call —
which compiles — and for a repeat call, where the outputs live, the device's
peak bytes).  The LAST line of stdout is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Phases (each a plain function of its sizes, so tests call them small on CPU):

  dense          LinearRegressionWithSGD and LogisticRegressionWithSGD
                 (SquaredL2Updater) over X (2**20, 1000) bf16 — the
                 north-star width, 2 GB on the device — made on the device
                 from the seed, handed to the trainers as host arrays
  host_streamed  GradientDescent.set_host_streaming over (2**19, 1000) f32
                 host rows, bf16 wire, prefetch 2, superstep K=8
  sparse         SVMWithSGD + L1Updater on RCV1-shaped BCOO (200000, 47236)
  serve          the dense model behind ``Server``; 64 submits == predict
  planner        ``plan.device_budget()`` must read ``memory_stats``
  data_parallel  (--chips 4 only) least squares over ``data_mesh()`` vs the
                 same config on a one-device mesh
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

#: the generator's noise level for least-squares targets
EPS = 0.1


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def configure_compile_cache() -> dict:
    """The persistent compile cache is placed from OUTSIDE when
    ``JAX_COMPILATION_CACHE_DIR`` is set (JAX reads it; no directory is set
    in code); otherwise at the fixed ``<checkout>/.jax_cache`` — the path is
    part of the cache key, so it never moves."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return {"phase": "compile_cache", "dir": env, "set_in_code": False}
    path = os.path.join(REPO, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return {"phase": "compile_cache", "dir": path, "set_in_code": True}


def _peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


def _where(x) -> list:
    return sorted(str(d) for d in x.devices())


def _require(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def _require_on_first_device(x, what: str) -> None:
    import jax

    _require(set(x.devices()) == {jax.devices()[0]},
             f"{what} lives on {_where(x)}, not on {jax.devices()[0]}")


def _require_learning(losses, what: str) -> None:
    import numpy as np

    losses = np.asarray(losses)
    _require(losses.size > 0 and bool(np.isfinite(losses).all()),
             f"{what}: non-finite loss in {losses.tolist()}")
    _require(losses[-1] < 0.5 * losses[0],
             f"{what}: last loss {losses[-1]} is not below half the first "
             f"{losses[0]}")


# -- data, made on the device from the seed ---------------------------------

def dense_generator(n: int, d: int):
    """Jitted ``key -> (X bf16 (n, d), y_linear, y_logistic, w_true)``.
    Targets are computed from the bf16-rounded X, so the generator's truth
    is exact for the stored data."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gen(key):
        kx, kw, kn, ku = jax.random.split(key, 4)
        X = jax.random.normal(kx, (n, d), jnp.bfloat16)
        w = jax.random.uniform(kw, (d,), jnp.float32, -1.0, 1.0)
        margin = jnp.dot(X, w.astype(jnp.bfloat16),
                         preferred_element_type=jnp.float32)
        y_lin = margin + EPS * jax.random.normal(kn, (n,), jnp.float32)
        y_log = (jax.random.uniform(ku, (n,)) < jax.nn.sigmoid(margin))
        return X, y_lin, y_log.astype(jnp.float32), w

    return gen


def make_dense_data(n: int, d: int, seed: int):
    """:func:`dense_generator`'s output as HOST arrays: one program on the
    device (host RNG at 2 GB is minutes), fetched once."""
    import jax
    import numpy as np

    t0 = time.perf_counter()
    out = jax.block_until_ready(
        dense_generator(n, d)(jax.random.PRNGKey(seed)))
    t_gen = time.perf_counter() - t0
    t0 = time.perf_counter()
    host = tuple(np.asarray(a) for a in out)
    t_fetch = time.perf_counter() - t0
    return host, {"generate_s": t_gen, "fetch_to_host_s": t_fetch}


def rcv1_columns_generator(n: int, d: int, nnz: int):
    """Jitted ``key -> (n_chunks, chunk, nnz)`` int32 feature columns: the
    n*d part of ``tpu_sgd.utils.rcv1_like_data`` (Zipf feature popularity
    sampled without replacement by Gumbel-top-k) as one device program —
    on the host those n*d draws are ~12 minutes at 200000 x 47236."""
    import jax
    import jax.numpy as jnp

    chunk = max(1, min(n, (1 << 28) // (4 * d)))  # <= 256 MB of keys
    n_chunks = -(-n // chunk)

    @jax.jit
    def gen(key):
        log_pop = -0.9 * jnp.log(jnp.arange(1, d + 1, dtype=jnp.float32))

        def rows(k):
            keys = log_pop[None, :] + jax.random.gumbel(k, (chunk, d))
            return jax.lax.approx_max_k(keys, nnz)[1].astype(jnp.int32)

        return jax.lax.map(rows, jax.random.split(key, n_chunks))

    return gen


def make_rcv1_like(n: int, d: int, nnz: int, seed: int):
    """``(X: BCOO, y, w_true)`` shaped like RCV1, by the recipe of
    ``tpu_sgd.utils.rcv1_like_data``: columns from
    :func:`rcv1_columns_generator`; the n*nnz part (lognormal values,
    L2-normalised rows, labels from a sparse linear model split at the
    median margin) in numpy as there."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.sparse import BCOO

    cols = np.asarray(
        rcv1_columns_generator(n, d, nnz)(jax.random.PRNGKey(seed)))
    cols = np.sort(cols.reshape(-1, nnz)[:n], axis=1)
    rng = np.random.default_rng(seed)
    pop = 1.0 / np.arange(1, d + 1) ** 0.9
    w = np.zeros((d,), np.float32)
    active = rng.choice(d, size=max(8, d // 100), replace=False,
                        p=pop / pop.sum())
    w[active] = rng.normal(scale=1.5, size=active.shape).astype(np.float32)
    vals = rng.lognormal(mean=0.0, sigma=0.5,
                         size=(n, nnz)).astype(np.float32)
    vals /= np.linalg.norm(vals, axis=1, keepdims=True)
    margins = np.einsum("ij,ij->i", vals, w[cols])
    y = (margins + 0.05 * rng.normal(size=n) > np.median(margins)).astype(
        np.float32)
    rows = np.repeat(np.arange(n, dtype=np.int32), nnz)
    idx = np.stack([rows, cols.reshape(-1)], axis=1)
    X = BCOO((jnp.asarray(vals.reshape(-1)), jnp.asarray(idx)),
             shape=(n, d), indices_sorted=True, unique_indices=True)
    return X, y, w


# -- phases ------------------------------------------------------------------

def _run_twice(alg, data):
    """``alg.run(data)`` twice on ONE algorithm object: the first call
    compiles, the second re-dispatches the optimizer's memoized program.
    Returns ``(model, losses, first_s, repeat_s)``."""
    import jax

    t0 = time.perf_counter()
    model = alg.run(data)
    jax.block_until_ready(model.weights)
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    model = alg.run(data)
    jax.block_until_ready(model.weights)
    repeat = time.perf_counter() - t0
    return model, alg.optimizer.loss_history, first, repeat


def phase_dense(n: int, d: int, iters: int, seed: int,
                weight_tol: float = 0.02):
    """Least squares and logistic+L2 through the model-level harness
    (``GeneralizedLinearAlgorithm.run`` — validation, the planner, the fused
    resident ``while_loop``), host arrays in as a user passes them.
    Returns ``(records, least_squares_model)``."""
    import numpy as np

    from tpu_sgd import (LinearRegressionWithSGD, LogisticRegressionWithSGD,
                         SquaredL2Updater)

    (X, y_lin, y_log, w_true), gen_times = make_dense_data(n, d, seed)
    shapes = {"X": [n, d], "X_dtype": str(X.dtype), "iterations": iters,
              "mini_batch_fraction": 0.1}
    records = []

    alg = LinearRegressionWithSGD(1.0, iters, mini_batch_fraction=0.1)
    alg.optimizer.set_convergence_tol(0.0)  # run every iteration
    lin, losses, first, repeat = _run_twice(alg, (X, y_lin))
    _require(len(losses) == iters, f"expected {iters} losses, got "
             f"{len(losses)}")
    _require_learning(losses, "least squares")
    _require_on_first_device(lin.weights, "least-squares weights")
    rel = float(np.linalg.norm(np.asarray(lin.weights) - w_true)
                / np.linalg.norm(w_true))
    _require(rel < weight_tol, f"least-squares weights are {rel:.4f} "
             f"(relative L2) from the generator's truth; tolerance "
             f"{weight_tol}")
    records.append({
        "phase": "dense.least_squares", **shapes, **gen_times,
        "first_call_s": first, "repeat_call_s": repeat,
        "loss_first": float(losses[0]), "loss_last": float(losses[-1]),
        "weights_rel_err_vs_truth": rel, "weight_tol": weight_tol,
        "schedule": getattr(alg.optimizer.last_plan, "schedule", None),
        "outputs_on": _where(lin.weights), "peak_bytes_in_use": _peak_bytes(),
    })

    alg = LogisticRegressionWithSGD(5.0, iters, reg_param=0.001,
                                    mini_batch_fraction=0.1)
    alg.optimizer.set_updater(SquaredL2Updater()).set_convergence_tol(0.0)
    log, losses, first, repeat = _run_twice(alg, (X, y_log))
    _require_learning(losses, "logistic + L2")
    _require_on_first_device(log.weights, "logistic weights")
    cos = float(np.dot(np.asarray(log.weights), w_true)
                / (np.linalg.norm(np.asarray(log.weights))
                   * np.linalg.norm(w_true)))
    records.append({
        "phase": "dense.logistic_l2", **shapes,
        "first_call_s": first, "repeat_call_s": repeat,
        "loss_first": float(losses[0]), "loss_last": float(losses[-1]),
        "weights_cosine_vs_truth": cos,
        "schedule": getattr(alg.optimizer.last_plan, "schedule", None),
        "outputs_on": _where(log.weights), "peak_bytes_in_use": _peak_bytes(),
    })
    return records, lin


def phase_host_streamed(n: int, d: int, supersteps: int, k: int, seed: int):
    """Host-resident f32 rows through the ingest pipeline: bf16 wire,
    double-buffered prefetch, K fused iterations per dispatch."""
    import jax
    import numpy as np

    from tpu_sgd import GradientDescent, LeastSquaresGradient, SimpleUpdater
    from tpu_sgd.utils import native

    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, d), dtype=np.float32)
    w_true = rng.uniform(-1.0, 1.0, size=(d,)).astype(np.float32)
    y = X @ w_true + EPS * rng.standard_normal(n, dtype=np.float32)
    iters, frac = supersteps * k, 0.1

    opt = (GradientDescent(LeastSquaresGradient(), SimpleUpdater())
           .set_step_size(1.0).set_num_iterations(iters)
           .set_mini_batch_fraction(frac).set_seed(seed)
           .set_convergence_tol(0.0)
           .set_host_streaming(True)
           .set_ingest_options(wire_dtype="bfloat16", prefetch_depth=2)
           .set_superstep(k))
    w0 = np.zeros((d,), np.float32)
    times = []
    for _ in range(2):
        t0 = time.perf_counter()
        w, losses = opt.optimize_with_history((X, y), w0)
        jax.block_until_ready(w)
        times.append(time.perf_counter() - t0)
    _require(len(losses) == iters, f"expected {iters} losses, got "
             f"{len(losses)}")
    _require_learning(losses, "host-streamed least squares")
    _require_on_first_device(w, "host-streamed weights")
    wire_bytes = iters * round(frac * n) * d * 2  # bf16 on the wire
    return {
        "phase": "host_streamed", "X": [n, d], "X_dtype": "float32",
        "host_bytes": int(X.nbytes), "wire_dtype": "bfloat16",
        "prefetch_depth": 2, "superstep_k": k, "iterations": iters,
        "first_call_s": times[0], "repeat_call_s": times[1],
        "feed_gb_per_s": wire_bytes / times[1] / 1e9,
        "gather": ("native" if os.path.exists(native._SAMPLER_PATH)
                   else "python"),
        "loss_first": float(losses[0]), "loss_last": float(losses[-1]),
        "outputs_on": _where(w), "peak_bytes_in_use": _peak_bytes(),
    }


def phase_sparse(n: int, d: int, nnz: int, iters: int, seed: int):
    """Hinge + L1 on BCOO features at RCV1's width, never densified."""
    import numpy as np

    from tpu_sgd import L1Updater, SVMWithSGD
    from tpu_sgd.ops.sparse import is_sparse

    t0 = time.perf_counter()
    X, y, _ = make_rcv1_like(n, d, nnz, seed)
    t_gen = time.perf_counter() - t0
    _require(is_sparse(X) and X.nse == n * nnz, "generator lost the BCOO")
    alg = SVMWithSGD(100.0, iters, reg_param=1e-5)
    alg.optimizer.set_updater(L1Updater()).set_convergence_tol(0.0)
    model, losses, first, repeat = _run_twice(alg, (X, y))
    _require(len(losses) == iters, f"expected {iters} losses, got "
             f"{len(losses)}")
    _require(bool(np.isfinite(losses).all()) and losses[-1] < losses[0],
             f"sparse hinge+L1 did not learn: {np.asarray(losses).tolist()}")
    _require_on_first_device(model.weights, "sparse weights")
    acc = float(np.mean(np.asarray(model.predict(X)) == np.asarray(y)))
    _require(acc > 0.6, f"sparse SVM train accuracy {acc:.3f} <= 0.6")
    return {
        "phase": "sparse.hinge_l1", "X": [n, d], "nnz_per_row": nnz,
        "nse": int(X.nse), "iterations": iters, "generate_s": t_gen,
        "first_call_s": first, "repeat_call_s": repeat,
        "loss_first": float(losses[0]), "loss_last": float(losses[-1]),
        "train_accuracy": acc,
        "weights_nonzero": int(np.count_nonzero(np.asarray(model.weights))),
        "outputs_on": _where(model.weights),
        "peak_bytes_in_use": _peak_bytes(),
    }


def phase_serve(model, requests: int, seed: int, tol: float = 1e-3):
    """The trained dense model behind ``Server``: every coalesced, bucketed
    answer must equal ``model.predict`` on the same row (to ``tol`` of the
    margin scale: a coalesced batch and a single row score through
    different bucket programs), and a float64 host matvec bounds both."""
    import numpy as np

    from tpu_sgd.serve import Server

    d = int(model.weights.shape[0])
    rng = np.random.default_rng(seed)
    rows = rng.standard_normal((requests, d)).astype(np.float32)
    w64 = np.asarray(model.weights, np.float64)
    scale = float(np.linalg.norm(w64))  # std of x.w for x ~ N(0, I)
    t0 = time.perf_counter()
    with Server(model=model, max_latency_s=0.002) as server:
        futures = [server.submit(r) for r in rows]
        served = np.asarray([f.result(timeout=120) for f in futures])
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        again = np.asarray([f.result(timeout=120)
                            for f in [server.submit(r) for r in rows]])
        repeat = time.perf_counter() - t0
        health = server.healthz()
    direct = np.asarray([model.predict(r) for r in rows])
    host = rows.astype(np.float64) @ w64 + model.intercept
    _require(served.shape == (requests,) and bool(np.isfinite(served).all()),
             f"served answers have shape {served.shape} or are non-finite")
    diff = float(np.max(np.abs(served - direct)))
    _require(diff <= tol * scale and np.array_equal(served, again),
             f"Server answers differ from model.predict by {diff} "
             f"(tolerance {tol * scale}) or changed between two rounds")
    # the TPU's default f32 matmul multiplies in bf16: ~2**-8 relative
    host_diff = float(np.max(np.abs(served - host)))
    _require(host_diff <= 0.02 * scale,
             f"Server answers are {host_diff} from the float64 host matvec "
             f"(tolerance {0.02 * scale})")
    return {
        "phase": "serve", "requests": requests, "d": d,
        "first_round_s": first, "repeat_round_s": repeat,
        "max_abs_diff_vs_predict": diff,
        "bitwise_equal_to_predict": bool(np.array_equal(served, direct)),
        "max_abs_diff_vs_host_f64": host_diff, "margin_scale": scale,
        "admit_count": health.get("admit_count"),
        "peak_bytes_in_use": _peak_bytes(),
    }


def phase_planner():
    """The planner must see the real device: ``device_budget`` reading
    ``memory_stats``.  ``"fallback"`` assumes 16 GB for a device it knows
    nothing of — on the chip that is a failure."""
    from tpu_sgd.plan import device_budget

    free, source = device_budget()
    _require(source == "memory_stats",
             f"plan.device_budget() answered from {source!r}, not from the "
             "device's memory_stats")
    return {"phase": "planner", "free_bytes": float(free), "source": source}


def phase_data_parallel(n: int, d: int, iters: int, seed: int, devices,
                        weight_tol: float = 1e-3):
    """Least squares over ``data_mesh(devices)`` against the same config on
    a one-device mesh: full batch (per-shard sampling keys make a fractional
    batch differ by design), rows sharded evenly on distinct devices, an
    all-reduce in the compiled program, agreeing weights."""
    import jax
    import numpy as np

    from tpu_sgd import LinearRegressionWithSGD, data_mesh
    from tpu_sgd.parallel.data_parallel import dp_run_fn, shard_dataset

    (X, y, _, w_true), gen_times = make_dense_data(n, d, seed)
    n_dev = len(devices)
    mesh = data_mesh(list(devices))

    Xd, yd, valid = shard_dataset(mesh, X, y)
    shards = Xd.addressable_shards
    _require(valid is None and len(shards) == n_dev
             and len({s.device for s in shards}) == n_dev
             and all(s.data.shape == (n // n_dev, d) for s in shards),
             f"X is not {n_dev} shards of {n // n_dev} rows on {n_dev} "
             f"distinct devices: "
             f"{[(str(s.device), s.data.shape) for s in shards]}")

    weights, timings = {}, {}
    for name, m in (("mesh", mesh), ("one_device", data_mesh(devices[:1]))):
        alg = LinearRegressionWithSGD(1.0, iters, mini_batch_fraction=1.0)
        alg.optimizer.set_mesh(m).set_convergence_tol(0.0)
        model, losses, first, repeat = _run_twice(alg, (X, y))
        _require_learning(losses, f"data-parallel least squares ({name})")
        weights[name] = np.asarray(model.weights)
        timings.update({f"{name}_first_call_s": first,
                        f"{name}_repeat_call_s": repeat,
                        f"{name}_loss_last": float(losses[-1])})
    diff = float(np.max(np.abs(weights["mesh"] - weights["one_device"]))
                 / np.max(np.abs(weights["one_device"])))
    _require(diff <= weight_tol, f"mesh and one-device weights differ by "
             f"{diff} (relative to max |w|); tolerance {weight_tol}")

    opt = alg.optimizer  # the same plugins and config on both meshes
    text = dp_run_fn(opt.gradient, opt.updater, opt.config, mesh,
                     False).lower(np.zeros((d,), np.float32), Xd, yd,
                                  opt.config.hyper()).compile().as_text()
    _require("all-reduce" in text, "no all-reduce in the compiled "
             "data-parallel run program")
    return {
        "phase": "data_parallel", "X": [n, d], "X_dtype": str(X.dtype),
        "iterations": iters, "mini_batch_fraction": 1.0, **gen_times,
        "devices": n_dev,
        "shards": [[str(s.device), list(s.data.shape)] for s in shards],
        "all_reduce_in_compiled_text": True, **timings,
        "max_rel_weight_diff": diff, "weight_tol": weight_tol,
        "weights_rel_err_vs_truth": float(
            np.linalg.norm(weights["mesh"] - w_true)
            / np.linalg.norm(w_true)),
        "peak_bytes_in_use": _peak_bytes(),
    }


# -- the run -----------------------------------------------------------------

def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4 runs the data-parallel phase and its "
                        "one-device comparison, and no other phase")
    args = parser.parse_args(argv)

    import jax

    emit(configure_compile_cache())
    devices = jax.devices()  # read once; a backend that cannot start raises
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu" or len(devices) != args.chips:
        emit({"ok": False, "device": device,
              "error": f"need {args.chips} TPU device(s)"})
        return 1
    try:
        if args.chips == 4:
            emit(phase_data_parallel(2**20, 1000, 5, args.seed, devices))
        else:
            records, model = phase_dense(2**20, 1000, 20, args.seed)
            for record in records:
                emit(record)
            emit(phase_host_streamed(2**19, 1000, 3, 8, args.seed))
            emit(phase_sparse(200_000, 47_236, 75, 20, args.seed))
            emit(phase_serve(model, 64, args.seed))
            emit(phase_planner())
    except BaseException as e:  # say so on the last line, then fail
        emit({"ok": False, "device": device, "error": repr(e)})
        raise
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
