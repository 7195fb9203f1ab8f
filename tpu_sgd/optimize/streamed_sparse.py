"""Host-streamed SGD over SPARSE (BCOO) features — never densified.

The RCV1-shaped workload (~47k features, ~0.1% nnz) previously had two
executions: fully device-resident BCOO (tpu_sgd/ops/sparse.py) or
nothing — ``set_host_streaming`` raised, because the dense streamed
driver's whole feed is dense row buffers.  This driver closes that gap
END-TO-END sparse: the dataset stays host-resident as CSR entry arrays,
every sampled batch ships as fixed-shape BCOO *components* ``(data,
indices)`` staged in host numpy (``tpu_sgd.io.sparse_wire``), and the
device step reassembles the BCOO inside the compiled program — no dense
``(rows, d)`` chunk is ever materialized on host or device, so the
wire carries ~``nnz/(rows*d)`` of the dense bytes (>= 100x on RCV1
shapes; measured by the ``obs`` wire counters, README "Compressed
wire").

Shape discipline (the eager-op shape-compile trap): a sparse batch
varies in BOTH rows and nse, so the staging pads to ONE ``(row_cap,
nse_cap)`` shape per build — ``row_cap`` by the dense driver's
binomial-cap rule, ``nse_cap`` by a deterministic pre-pass over the
whole run's sample sequence (``io.sparse_wire.plan_sparse_batches``;
the sample is deterministic in ``(seed, i)``, so the cap — and the one
compiled body program — is identical across replays and resumes,
``assert_compile_count``-pinned in tests/test_sparse_wire.py).  Padding
entries are null entries (0.0 at (0, 0)) contributing exact zeros.

Same driver contracts as ``optimize/streamed.py``: bernoulli sampling
(the sparse support surface) or full batch, deterministic in
``default_rng(seed + i)`` and bitwise-identical to the dense streamed
driver's sampled row sequence; double-buffered prefetch
(``Prefetcher``, bitwise A/B vs depth 0); superstep fusion
(``superstep_k=K``: one ``lax.scan`` program over the K-batch sparse
superchunk, per-step ys replayed through the shared
``_replay_fused_steps`` — tail supersteps pad with all-False valid
rows); checkpoint/resume and cooperative preemption at superstep
boundaries, bitwise vs uninterrupted.  Full-batch feeds transfer the
components ONCE and scan over them; ``resident_cadence >= 2`` on that
feed escalates to the shared whole-run resident driver
(``optimize/resident_driver.py``) — the fixed-nse BCOO body becomes a
``step_fn`` feed variant of the ONE ``lax.while_loop`` program, one
dispatch per run instead of one per superstep.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpu_sgd.config import SGDConfig
from tpu_sgd.ops.gradients import Gradient
from tpu_sgd.ops.updaters import Updater

#: compiled sparse step/superstep memo — the sparse twin of
#: ``streamed._RESIDENT_LOOPS``: repeated runs / supervisor resume
#: attempts with an unchanged (plugin pair, config, K, feed geometry)
#: reuse the one compiled program instead of re-tracing per call.
#: Bounded FIFO so a long-lived process cycling configs doesn't pin
#: dead programs forever.
_SPARSE_PROGRAMS: OrderedDict = OrderedDict()
_SPARSE_PROGRAMS_MAX = 8

#: memo-key contract (graftlint memo-key rule): the cache key is built
#: from exactly these roots — the optimizer plugins, the config, the
#: superstep knob, and the feed geometry (``n``/``d`` and the derived
#: row/nse caps all come from X's host CSR relayout and the config's
#: sampling parameters)
GRAFTLINT_MEMO = {
    "_SPARSE_PROGRAMS": ("gradient", "updater", "config", "superstep_k",
                         "resident_cadence", "X", "n", "d"),
}


def _bcoo(data, idx, rows: int, d: int):
    from jax.experimental.sparse import BCOO

    return BCOO((data, idx), shape=(rows, d))


def _sparse_step_fn(gradient, updater, step_cfg, rows: int, d: int):
    """Jitted single sparse step: rebuild the batch BCOO from its
    transferred components inside the program, then the SAME
    ``make_step`` body as every other driver."""
    from tpu_sgd.optimize.gradient_descent import make_step

    base = make_step(gradient, updater, step_cfg)

    def fn(w, data, idx, yb, i, rv, hyper, valid):
        return base(w, _bcoo(data, idx, rows, d), yb, i, rv, hyper, valid)

    return jax.jit(fn)


def _sparse_superstep_fn(gradient, updater, step_cfg, rows: int, d: int):
    """Jitted K-fused sparse superstep: ``lax.scan`` over the sparse
    superchunk's leading step axis, one BCOO reassembly per step inside
    the one compiled program; ys per ``pack_step_ys``."""
    from tpu_sgd.optimize.gradient_descent import make_step, pack_step_ys

    step = make_step(gradient, updater, step_cfg)

    def fn(w, rv, hyper, i0, Ds, Is, Ys, Vs):
        idxs = i0 + jnp.arange(Ds.shape[0], dtype=jnp.int32)

        def body(carry, xs):
            cw, crv = carry
            i, dt, it, yt, vt = xs
            new_w, loss_i, new_rv, c = step(
                cw, _bcoo(dt, it, rows, d), yt, i, crv, hyper, vt)
            return (new_w, new_rv), pack_step_ys(cw, new_w, loss_i,
                                                 new_rv, c)

        (w, _), out = jax.lax.scan(body, (w, rv), (idxs, Ds, Is, Ys, Vs))
        return w, out

    return fn


def _sparse_resident_step_fn(gradient, updater, step_cfg, rows: int,
                             d: int):
    """Per-iteration unit for the whole-run resident driver over the
    ONE shared sparse batch: the fixed-nse BCOO reassembles from its
    once-transferred components inside the while-loop body — the
    sparse feed is just another ``step_fn`` variant of the single
    driver (``resident_driver.ResidentLoop``), not a second loop.
    UNJITTED: the loop owns the jit."""
    from tpu_sgd.optimize.gradient_descent import make_step

    base = make_step(gradient, updater, step_cfg)

    def fn(w, i, rv, hyper, data, idx, yb, valid):
        return base(w, _bcoo(data, idx, rows, d), yb, i, rv, hyper, valid)

    return fn


def _sparse_shared_superstep_fn(gradient, updater, step_cfg, rows: int,
                                d: int, k: int):
    """Jitted K-fused superstep over ONE shared sparse batch (the
    full-batch feed: components transferred once, the scan reuses
    them)."""
    from tpu_sgd.optimize.gradient_descent import make_step, pack_step_ys

    step = make_step(gradient, updater, step_cfg)
    K = int(k)

    def fn(w, rv, hyper, i0, data, idx, yb, valid):
        idxs = i0 + jnp.arange(K, dtype=jnp.int32)

        def body(carry, i):
            cw, crv = carry
            new_w, loss_i, new_rv, c = step(
                cw, _bcoo(data, idx, rows, d), yb, i, crv, hyper, valid)
            return (new_w, new_rv), pack_step_ys(cw, new_w, loss_i,
                                                 new_rv, c)

        (w, _), out = jax.lax.scan(body, (w, rv), idxs)
        return w, out

    return fn


def optimize_host_streamed_sparse(
    gradient: Gradient,
    updater: Updater,
    config: SGDConfig,
    X,
    y: np.ndarray,
    initial_weights,
    device=None,
    listener=None,
    checkpoint_manager=None,
    checkpoint_every: int = 10,
    prefetch_depth: int = 2,
    retry_policy=None,
    stop_signal=None,
    superstep_k: int = 1,
    resident_cadence: int = 0,
    wire_compress=None,
) -> Tuple[jax.Array, np.ndarray]:
    """Run mini-batch SGD with the SPARSE dataset resident on the host.

    ``X`` is a host-side BCOO (``tpu_sgd.ops.sparse``); see the module
    docstring for the staging/shape contracts.  Returns ``(weights,
    loss_history)`` with the dense streamed driver's exact bookkeeping
    semantics (loss history includes the previous iteration's reg
    value, convergence tolerance early exit, checkpoint cadence,
    boundary preemption).

    ``resident_cadence >= 2`` (with ``superstep_k >= 2``) on the
    FULL-BATCH feed moves the whole run loop on device: the fixed-nse
    BCOO components transfer once and the run is ONE
    ``lax.while_loop`` dispatch of the same resident driver the dense
    feeds use (``optimize/resident_driver.py``) — the sparse slab is a
    ``step_fn`` feed variant of that one program, with the cadence
    ``io_callback`` ring replaying through the shared
    ``_replay_fused_steps``.  Host-sampled (bernoulli) sparse
    streaming keeps the superstep driver with a warning (the per-batch
    host hop IS the data feed — the composition grid's recorded
    fallback cell)."""
    import time as _time

    from tpu_sgd.io import Prefetcher
    from tpu_sgd.io.integrity import seal, verify
    from tpu_sgd.io.sparse_wire import (bcoo_to_csr_host,
                                        plan_sparse_batches,
                                        stage_sparse_batch)
    from tpu_sgd.obs.counters import record_wire
    from tpu_sgd.obs.spans import span
    from tpu_sgd.optimize.gradient_descent import (_replay_fused_steps,
                                                   observed_loop_tail)
    from tpu_sgd.reliability.failpoints import corruptpoint
    from tpu_sgd.utils.events import RunEvent

    cfg = config
    if cfg.mini_batch_fraction < 1.0 and cfg.sampling != "bernoulli":
        raise NotImplementedError(
            "host-streamed sparse training supports bernoulli sampling "
            f"or full batch (got sampling={cfg.sampling!r}; sliced/"
            "indexed need a dense row layout)"
        )
    if wire_compress is not None:
        import warnings

        warnings.warn(
            "wire_compress applies to the update-shaped wires (gradient "
            "all-reduce, totals merge); the sparse FEED is already "
            "compressed — BCOO components are the wire format here",
            RuntimeWarning, stacklevel=3,
        )
    if device is None:
        device = jax.devices()[0]
    indptr, cols, vals, (n, d) = bcoo_to_csr_host(X)
    w = jnp.asarray(initial_weights)
    if not jnp.issubdtype(w.dtype, jnp.inexact):
        w = w.astype(jnp.float32)
    w = jax.device_put(w, device)
    if n == 0:
        return w, np.zeros((0,), np.float32)
    yh = np.asarray(y)
    if not np.issubdtype(yh.dtype, np.inexact):
        yh = yh.astype(np.float32)

    step_cfg = cfg.replace(mini_batch_fraction=1.0)
    frac = cfg.mini_batch_fraction
    full_batch = frac >= 1.0
    if full_batch:
        cap = n
    else:
        sigma = np.sqrt(n * frac * (1.0 - frac))
        cap = int(min(n, np.ceil(n * frac + 6.0 * sigma + 8)))

    def sample_rows(i: int) -> np.ndarray:
        """THE per-iteration sampled-row rule — identical to the dense
        streamed driver's bernoulli draw (``default_rng(seed + i)``
        mask, uniformly-truncated overflow), shared by the nse-cap
        pre-pass and the producer so the planned cap can never miss a
        batch."""
        if full_batch:
            return np.arange(n, dtype=np.int64)
        rng = np.random.default_rng(cfg.seed + i)
        m = rng.random(n) < frac
        idx = np.nonzero(m)[0]
        if idx.shape[0] > cap:
            idx = rng.permutation(idx)[:cap]
        return idx

    # fixed nse per staged batch, planned over the WHOLE run (the
    # sample sequence is deterministic, so a resumed run plans the
    # same cap and reuses the same compiled body)
    if full_batch:
        nse_cap = max(1, int(vals.shape[0]))
    else:
        nse_cap = plan_sparse_batches(indptr, sample_rows,
                                      cfg.num_iterations, cap)

    K = max(1, int(superstep_k))
    C = max(0, int(resident_cadence))
    if C >= 2 and K <= 1:
        import warnings

        warnings.warn(
            "device residency rides the fused superstep executor; pass "
            "superstep_k >= 2 (or let the planner pick K) to engage it",
            RuntimeWarning, stacklevel=3,
        )
        C = 0
    if C >= 2 and not full_batch:
        import warnings

        warnings.warn(
            "device residency applies to the full-batch sparse feed "
            "(components transfer once); a bernoulli-sampled sparse "
            "stream's per-batch host hop IS the data feed, so the "
            "fused superstep driver runs — the recorded "
            "composition-grid cell for this feed "
            "(tests/test_composition.py, feed=sparse-bernoulli x "
            "resident)",
            RuntimeWarning, stacklevel=3,
        )
        C = 0

    _, reg_val = updater.compute(
        w, jnp.zeros_like(w), 0.0, jnp.asarray(1, jnp.int32),
        cfg.reg_param
    )
    # the step size and the regulariser: operands of every program below
    # (``make_step``), placed once
    hyper = jax.device_put(cfg.hyper(), device)

    def stage(i: int):
        """One batch's host assembly: CSR row gather + fixed-shape pad
        (``io.sparse_wire`` failpoint) — pure host numpy."""
        rows = sample_rows(i)
        data, idx, valid = stage_sparse_batch(
            indptr, cols, vals, rows, cap, nse_cap)
        yb = np.zeros((cap,), yh.dtype)
        yb[: rows.shape[0]] = yh[rows]
        return data, idx, yb, valid

    def sample(i: int):
        """Stage + transfer — the per-iteration producer (runs on the
        prefetch worker inside the retry scope).  The staged components
        are a checksummed FRAME (tpu_sgd/io/integrity.py): sealed after
        assembly, passed through the ``io.sparse_chunk`` corrupting
        failpoint, verified here at the consume boundary — a damaged
        entry array, label, or mask raises typed IntegrityError inside
        the retry scope and the deterministic re-stage heals BITWISE."""
        data, idx, yb, valid = stage(i)
        ck = seal(data, idx, yb, valid)
        data, idx, yb, valid = corruptpoint(
            "io.sparse_chunk", (data, idx, yb, valid))
        verify("io.sparse_chunk", ck, data, idx, yb, valid)
        record_wire(
            "bcoo",
            logical_nbytes=int(cap * d * 4 + yb.nbytes + valid.nbytes),
            physical_nbytes=int(data.nbytes + idx.nbytes + yb.nbytes
                                + valid.nbytes))
        return (jax.device_put(data, device), jax.device_put(idx, device),
                jax.device_put(yb, device), jax.device_put(valid, device))

    def sample_super(base: int):
        """Superstep producer: K staged batches assembled into one
        ``(K, ...)`` sparse superchunk, one ``device_put`` per leaf; a
        tail superstep pads missing steps with null entries and
        all-False valid rows (no-op updates, fixed shape)."""
        steps = min(K, cfg.num_iterations - base + 1)
        Ds = np.zeros((K, nse_cap), vals.dtype)
        Is = np.zeros((K, nse_cap, 2), np.int32)
        Ys = np.zeros((K, cap), yh.dtype)
        Vs = np.zeros((K, cap), bool)
        for t in range(steps):
            Ds[t], Is[t], Ys[t], Vs[t] = stage(base + t)
        ck = seal(Ds, Is, Ys, Vs)
        Ds, Is, Ys, Vs = corruptpoint(
            "io.sparse_chunk", (Ds, Is, Ys, Vs))
        verify("io.sparse_chunk", ck, Ds, Is, Ys, Vs)
        record_wire(
            "bcoo",
            logical_nbytes=int(K * cap * d * 4 + Ys.nbytes + Vs.nbytes),
            physical_nbytes=int(Ds.nbytes + Is.nbytes + Ys.nbytes
                                + Vs.nbytes))
        return (jax.device_put(Ds, device), jax.device_put(Is, device),
                jax.device_put(Ys, device), jax.device_put(Vs, device))

    # -- compiled programs (memoized; see GRAFTLINT_MEMO) -------------------
    # kind stays at key index 4 (pinned in tests); the resident kind
    # appends its cadence, which the other kinds don't key on
    if K > 1 and C >= 2:
        kind = "resident"
        prog_key = (gradient, updater, cfg, K, kind, cap, nse_cap, d, C)
    else:
        if K > 1:
            kind = "shared_super" if full_batch else "super"
        else:
            kind = "step"
        prog_key = (gradient, updater, cfg, K, kind, cap, nse_cap, d)
    prog = _SPARSE_PROGRAMS.get(prog_key)
    if prog is None:
        if kind == "step":
            prog = _sparse_step_fn(gradient, updater, step_cfg, cap, d)
        elif kind == "super":
            prog = jax.jit(_sparse_superstep_fn(
                gradient, updater, step_cfg, cap, d))
        elif kind == "resident":
            # the ONE whole-run driver (optimize/resident_driver.py):
            # the sparse shared batch is a step_fn feed variant of the
            # same while-loop program the dense feeds dispatch
            from tpu_sgd.optimize.resident_driver import ResidentLoop

            prog = ResidentLoop(
                _sparse_resident_step_fn(gradient, updater, step_cfg,
                                         cap, d),
                cfg, K, C)
        else:
            prog = jax.jit(_sparse_shared_superstep_fn(
                gradient, updater, step_cfg, cap, d, K))
        _SPARSE_PROGRAMS[prog_key] = prog
        while len(_SPARSE_PROGRAMS) > _SPARSE_PROGRAMS_MAX:
            _SPARSE_PROGRAMS.popitem(last=False)

    # -- bookkeeping state (the dense streamed driver's exact recipe) -------
    if listener is not None:
        listener.on_run_start(cfg)
    losses = []
    start_iter = 1
    config_key = repr((type(gradient).__name__, type(updater).__name__,
                       cfg))
    if checkpoint_manager is not None:
        state = checkpoint_manager.restore()
        if state is not None:
            if state["config_key"] and state["config_key"] != config_key:
                import warnings

                warnings.warn(
                    "checkpoint config differs from current config; "
                    "resuming anyway",
                    RuntimeWarning, stacklevel=3,
                )
            w = jax.device_put(jnp.asarray(state["weights"]), device)
            reg_val = state["reg_val"]
            losses = list(np.asarray(state["loss_history"], np.float32))
            start_iter = state["iteration"] + 1
    t_run = _time.perf_counter()
    converged = False

    def _save(ii, w_np, rv):
        checkpoint_manager.save(ii, np.asarray(w_np), rv,
                                np.asarray(losses), config_key)

    def _end():
        if listener is not None:
            listener.on_run_end(RunEvent(
                event="run_completed",
                num_iterations=len(losses),
                final_loss=losses[-1] if losses else None,
                converged_early=converged,
                wall_time_s=_time.perf_counter() - t_run,
            ))

    if K > 1 and C >= 2:
        # Whole-run resident sparse driver: the shared fixed-nse BCOO
        # components transfer ONCE (inside the ingest retry scope,
        # like the dense full-batch transfer) and the entire
        # converged-or-budget-exhausted run is one dispatch of the
        # shared while-loop program; window rings replay through the
        # same ResidentBookkeeper/_replay_fused_steps bookkeeping as
        # every resident feed, so history, events, convergence, and
        # checkpoint bytes are exactly the superstep driver's.
        from tpu_sgd.optimize.resident_driver import ResidentBookkeeper

        if start_iter <= cfg.num_iterations:
            def _t0():
                return sample(start_iter)

            shared = (retry_policy.call(_t0)
                      if retry_policy is not None else _t0())
            hooks = ResidentBookkeeper(
                cfg, K, C, losses=losses, reg_val=reg_val,
                start_iter=start_iter, listener=listener,
                save_cb=(_save if checkpoint_manager is not None
                         else None),
                save_every=checkpoint_every,
                stop_signal=stop_signal,
                retry_policy=retry_policy)
            w_np, converged = prog.run(w, reg_val, start_iter,
                                       (hyper,) + tuple(shared), hooks)
            w = jax.device_put(jnp.asarray(w_np), device)
            reg_val = hooks.reg_val
        _end()
        return w, np.asarray(losses, np.float32)

    if K > 1:
        from tpu_sgd.reliability.supervisor import TrainingPreempted

        if full_batch:
            if start_iter <= cfg.num_iterations:
                def _t():
                    return sample(start_iter)

                shared = (retry_policy.call(_t)
                          if retry_policy is not None else _t())
            prefetch = None
        else:
            prefetch = Prefetcher(
                sample_super,
                range(start_iter, cfg.num_iterations + 1, K),
                depth=prefetch_depth, retry_policy=retry_policy)
            nxt = (next(prefetch)
                   if start_iter <= cfg.num_iterations else None)
        try:
            i0 = start_iter
            while i0 <= cfg.num_iterations and not converged:
                steps = min(K, cfg.num_iterations - i0 + 1)
                t0 = _time.perf_counter()
                with span("train.superstep", i0=i0, steps=steps):
                    if full_batch:
                        w_dev, ys = prog(
                            w, jnp.asarray(reg_val, jnp.float32), hyper,
                            jnp.asarray(i0, jnp.int32), *shared)
                    else:
                        Ds, Is, Ys, Vs = nxt
                        w_dev, ys = prog(
                            w, jnp.asarray(reg_val, jnp.float32), hyper,
                            jnp.asarray(i0, jnp.int32), Ds, Is, Ys, Vs)
                        if i0 + K <= cfg.num_iterations:
                            nxt = next(prefetch)
                    ys_host = tuple(np.asarray(a) for a in ys)
                dt = _time.perf_counter() - t0
                t_last, reg_val, converged = _replay_fused_steps(
                    ys_host, i0, steps, losses, reg_val, cfg,
                    listener=listener, wall_dt=dt / steps,
                    save_cb=(_save if checkpoint_manager is not None
                             else None),
                    save_every=checkpoint_every,
                )
                if converged or steps < K:
                    w = jax.device_put(jnp.asarray(ys_host[0][t_last]),
                                       device)
                else:
                    w = w_dev
                if (not converged and stop_signal is not None
                        and stop_signal()):
                    boundary = i0 + steps - 1
                    if checkpoint_manager is not None:
                        checkpoint_manager.save(
                            # graftlint: disable=host-sync -- preemption save: fires once at the superstep boundary unwind, not per trip
                            boundary, np.asarray(w), reg_val,
                            np.asarray(losses), config_key)
                    raise TrainingPreempted(boundary)
                i0 += steps
        finally:
            if prefetch is not None:
                prefetch.close()
        _end()
        return w, np.asarray(losses, np.float32)

    # -- K=1 per-iteration loop ---------------------------------------------
    if full_batch:
        shared = None
        if start_iter <= cfg.num_iterations:
            def _t1():
                return sample(start_iter)

            shared = (retry_policy.call(_t1)
                      if retry_policy is not None else _t1())
        prefetch = None
    else:
        prefetch = Prefetcher(sample,
                              range(start_iter, cfg.num_iterations + 1),
                              depth=prefetch_depth,
                              retry_policy=retry_policy)
    try:
        nxt = None
        if prefetch is not None and start_iter <= cfg.num_iterations:
            nxt = next(prefetch)
        i = start_iter
        while i <= cfg.num_iterations and not converged:
            t0 = _time.perf_counter()
            with span("train.step", i=i):
                data, idx, yb, valid = shared if full_batch else nxt
                new_w, loss_i, new_reg, c = prog(
                    w, data, idx, yb, jnp.asarray(i, jnp.int32),
                    jnp.asarray(reg_val, jnp.float32), hyper, valid)
                if prefetch is not None and i < cfg.num_iterations:
                    nxt = next(prefetch)
                # the observed sparse streamed driver shares the dense
                # driver's contract: one barrier per step, then each
                # scalar fetched exactly once
                # graftlint: disable=host-sync -- observed driver: one barrier per step precedes the scalar reads below
                new_w = jax.block_until_ready(new_w)
            dt = _time.perf_counter() - t0
            # the shared observed-loop TAIL (one definition for this
            # driver and the dense streamed driver — the PR 9 review's
            # flagged duplication, extracted to the observe_step home):
            # barrier above, then each scalar fetched exactly once,
            # then the cooperative-preemption check
            w, reg_val, converged = observed_loop_tail(  # graftlint: disable=host-sync -- observed driver: the per-step scalar fetches ARE the contract (one barrier above, each scalar fetched once inside the shared helper)
                i, w, new_w, loss_i, new_reg, c, losses, reg_val, cfg,
                listener=listener, wall_dt=dt,
                save_cb=(_save if checkpoint_manager is not None
                         else None),
                save_every=checkpoint_every, stop_signal=stop_signal,
            )
            i += 1
    finally:
        if prefetch is not None:
            prefetch.close()
    _end()
    return w, np.asarray(losses, np.float32)
