"""Chunked-gather driver for block-ALIGNED sufficient-statistics SGD.

Round-4's decomposition experiment (its script was deleted in PR 23; the
record ``GRAM_SCAN_EXPERIMENT.json`` stays) showed the 0.024 ms aligned-gram iteration spends roughly half its time
OUTSIDE the two (d, d) prefix reads — per-iteration loop bookkeeping and
dispatch.  This driver amortizes that: an outer ``while_loop`` advances
``chunk_iters`` iterations at a time, gathering ALL of the chunk's window
endpoints from the prefix stacks in four bulk ``jnp.take`` ops (2·K (d, d)
rows — the same bytes the per-iteration driver reads, in K-fold larger
transfers), then an inner ``fori_loop`` runs the K updates from the
gathered registers.

The CONTRACT IS UNCHANGED from ``make_run`` (``optimize/
gradient_descent.py``): the same per-iteration ``fold_in(seed, i)``
window stream, per-iteration loss history including the previous
iteration's reg value, realized-count normalization, and per-iteration
weight-delta convergence — a converged run masks the chunk's remaining
updates to no-ops and exits at the chunk boundary, recording exactly as
many losses as the per-iteration driver would.  Applies to block-aligned
windows only (virtual statistics, or resident stats in aligned mode)
with sliced sampling — exactly the regime the headline measures.

HARDWARE VERDICT (2026-08-01, ``GRAM_SCAN_EXPERIMENT.json``): on the
TPU v5 lite the gather LOSES — 0.556 ms/iter (trajectory-clean) vs
0.0259 ms/iter for the per-iteration driver, because ``jnp.take`` of
K prefix pairs materializes 2·K (d, d) blocks through HBM while the
per-iteration driver's two dynamic slices stay fused; the bookkeeping
it amortizes measured only ~0.0036 ms/iter (14%).  The driver stays
OPT-IN via ``GradientDescent.set_gram_options(chunk_iters=K)`` — it
still wins ~1.4–2.6× on CPU hosts — and the planner default remains
the per-iteration contract (the round-5 decision).

FOLLOW-UP CLOSED (PR 5): the weights_agree-gated product_chunked vs
full_contract comparison the JSON asked for was computed by the
experiment script itself (``product_chunked_wins`` + ``verdict``
fields) and the recorded verdict keeps the per-iteration
default.  The dispatch-tax half of the original motivation — the
~44–65 ms fixed cost plus per-iteration host slop — is attacked from
the other side by the superstep executor
(``GradientDescent.set_superstep``; README "Fused stepping"), which
fuses the HOST-dispatched paths where that tax actually dominates.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from tpu_sgd.config import SGDConfig
from tpu_sgd.ops.gram import (aligned_window_blocks, aligned_window_k1,
                              aligned_window_terms)
from tpu_sgd.ops.updaters import Updater


def make_chunked_gram_run(
    updater: Updater,
    config: SGDConfig,
    *,
    n: int,
    block_rows: int,
    chunk_iters: int = 16,
):
    """Build the chunked aligned-gram loop as one traceable function.

    ``run(initial_weights, data: GramData, y) -> (weights, loss_history,
    n_recorded)`` — the ``make_run`` return contract.  ``y`` is accepted
    for signature parity and never read (the statistics carry it).
    """
    cfg = config
    K = int(chunk_iters)
    if K < 1:
        raise ValueError(f"chunk_iters must be positive, got {chunk_iters}")
    key = jax.random.PRNGKey(cfg.seed)
    m = max(1, round(cfg.mini_batch_fraction * n))
    B = int(block_rows)
    nbf = n // B
    mb = aligned_window_blocks(m, B, nbf)
    count = float(mb * B)
    check_conv = cfg.convergence_tol > 0.0
    num_iters = cfg.num_iterations

    def k1_of(i):
        # EXACTLY the per-iteration driver's sliced-window stream:
        # fold_in(key, i) -> randint start (make_step's draw) -> the
        # SHARED aligned clamp (ops/gram.py aligned_window_k1)
        k = jax.random.fold_in(key, i)
        start = jax.random.randint(k, (), 0, max(1, n - m + 1))
        return aligned_window_k1(start, n, m, B, nbf, mb).astype(jnp.int32)

    def run(initial_weights, data, y, valid=None):
        del y, valid  # statistics-only execution
        PG, Pb, Pyy = data.PG, data.Pb, data.Pyy
        sd = PG.dtype
        w0 = initial_weights
        _, reg_val0 = updater.compute(
            w0, jnp.zeros_like(w0), 0.0, jnp.asarray(1, jnp.int32),
            cfg.reg_param,
        )
        losses0 = jnp.full((num_iters,), jnp.nan, jnp.float32)

        def cond(carry):
            base, _, _, _, _, converged = carry
            return (base <= num_iters) & jnp.logical_not(converged)

        def chunk_body(carry):
            base, w, reg_val, losses, n_rec, conv = carry
            idx = base + jnp.arange(K, dtype=jnp.int32)
            k1s = jax.vmap(k1_of)(idx)
            k2s = k1s + mb
            # the chunk's window stats in six bulk gathers (the same
            # bytes as K iterations of per-row dynamic slices); indices
            # are provably in [0, nbf] against (nbf+1)-row stacks, so
            # mode="clip" (XLA's native clamped gather) skips the
            # default fill-mode bounds selects on the hot path
            take = partial(jnp.take, axis=0, mode="clip")
            Gd = take(PG, k2s) - take(PG, k1s)
            bd = take(Pb, k2s) - take(Pb, k1s)
            yyd = take(Pyy, k2s) - take(Pyy, k1s)

            def inner(t, ic):
                w, reg_val, losses, n_rec, conv = ic
                i = idx[t]
                active = jnp.logical_not(conv) & (i <= num_iters)
                g_sum, loss_sum = aligned_window_terms(
                    Gd[t], bd[t], yyd[t], w.astype(sd))
                loss_i = (loss_sum / count).astype(jnp.float32) + reg_val
                g_mean = (g_sum / count).astype(w.dtype)
                new_w, new_reg = updater.compute(
                    w, g_mean, cfg.step_size, i, cfg.reg_param
                )
                losses = jnp.where(
                    active, losses.at[n_rec].set(loss_i), losses
                )
                n_rec = n_rec + active.astype(n_rec.dtype)
                if check_conv:
                    diff = jnp.sqrt(jnp.sum((new_w - w) ** 2))
                    w_norm = jnp.sqrt(jnp.sum(new_w ** 2))
                    conv = conv | (
                        active & (i > 1)
                        & (diff < cfg.convergence_tol
                           * jnp.maximum(w_norm, 1.0))
                    )
                w = jnp.where(active, new_w, w)
                reg_val = jnp.where(active, new_reg, reg_val)
                return (w, reg_val, losses, n_rec, conv)

            w, reg_val, losses, n_rec, conv = jax.lax.fori_loop(
                0, K, inner, (w, reg_val, losses, n_rec, conv)
            )
            return (base + K, w, reg_val, losses, n_rec, conv)

        carry = (
            jnp.asarray(1, jnp.int32),
            w0,
            reg_val0,
            losses0,
            jnp.asarray(0, jnp.int32),
            jnp.asarray(False),
        )
        _, w, _, losses, n_rec, _ = jax.lax.while_loop(
            cond, chunk_body, carry
        )
        return w, losses, n_rec

    return run
