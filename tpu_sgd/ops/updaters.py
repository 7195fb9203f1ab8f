"""Weight updaters: the regularization axis of the optimizer plugin boundary.

Reference parity: [U] mllib/optimization/Updater.scala (SURVEY.md §2 #4).
Contract: ``compute(weights_old, gradient, step_size, iter, reg_param) ->
(weights_new, reg_val)`` where the effective step decays as
``step_size / sqrt(iter)`` and ``reg_val`` is the regularization value of the
*new* weights (used by the optimizer to report regularized loss one iteration
later — see SURVEY.md §5.5 loss-history contract).

All updaters are pure jnp functions, safe under ``jit`` and inside
``shard_map`` (they run replicated on every core; deterministic replication
replaces the reference's TorrentBroadcast, SURVEY.md §5.8).

``step_size`` and ``reg_param`` may be TRACED scalars, as ``iter_num`` always
was: every compiled program of the optimizer (``make_step`` and what is built
from it) takes the two as operands (``config.Hyper``), so that one program
serves every step size and regulariser of a tuning grid, and hands ``compute``
its tracers.  ``compute`` therefore does ``jax.numpy`` arithmetic on them and
nothing else: no ``float(step_size)``, no ``if reg_param > 0`` (``jnp.where``),
no numpy call.  An updater that needs a concrete value fails at trace with a
``TypeError`` that names this contract (``gradient_descent._update``); there
is no option that closes a program over the values instead.  Called outside a
program (a test, the observed driver's probe of the initial ``reg_val``) the
same ``compute`` is handed Python floats and behaves as it always did.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

Array = jax.Array


class Updater:
    """Base plugin. Subclasses implement :meth:`compute`."""

    def compute(
        self,
        weights_old: Array,
        gradient: Array,
        step_size,  # a float or a traced scalar
        iter_num: Array,
        reg_param,  # a float or a traced scalar
    ) -> Tuple[Array, Array]:
        raise NotImplementedError


class SimpleUpdater(Updater):
    """Plain SGD step, no regularization: ``w' = w - (eta/sqrt(t)) * g``."""

    def compute(self, weights_old, gradient, step_size, iter_num, reg_param):
        this_step = step_size / jnp.sqrt(jnp.asarray(iter_num, jnp.float32))
        w = weights_old - this_step * gradient
        return w, jnp.zeros((), w.dtype)


class L1Updater(Updater):
    """Lasso prox step: gradient step then soft-thresholding.

    Parity ([U] Updater.scala L1Updater): shrinkage = reg_param * eta_t applied
    to the *post-step* weights; reg_val = reg_param * ||w'||_1.  This is the
    "easy to get subtly wrong" prox the survey calls out (SURVEY.md §7 hard
    parts) — property-tested against the closed form in tests/test_updaters.py.
    """

    def compute(self, weights_old, gradient, step_size, iter_num, reg_param):
        this_step = step_size / jnp.sqrt(jnp.asarray(iter_num, jnp.float32))
        w = weights_old - this_step * gradient
        shrink = reg_param * this_step
        w = jnp.sign(w) * jnp.maximum(jnp.abs(w) - shrink, 0.0)
        reg_val = reg_param * jnp.sum(jnp.abs(w))
        return w, reg_val


class SquaredL2Updater(Updater):
    """Ridge step in the L2-regularized subgradient form.

    Parity ([U] Updater.scala SquaredL2Updater):
    ``w' = w * (1 - eta_t * reg) - eta_t * g``;
    ``reg_val = 0.5 * reg * ||w'||^2``.
    """

    def compute(self, weights_old, gradient, step_size, iter_num, reg_param):
        this_step = step_size / jnp.sqrt(jnp.asarray(iter_num, jnp.float32))
        w = weights_old * (1.0 - this_step * reg_param) - this_step * gradient
        reg_val = 0.5 * reg_param * jnp.sum(w * w)
        return w, reg_val
