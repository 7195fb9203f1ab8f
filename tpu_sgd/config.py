"""Configuration for the TPU-native SGD framework.

Mirrors the reference's two-tier config system (SURVEY.md §5.6): Spark exposes
builder-style setters on the optimizer/algorithm (``setStepSize``,
``setNumIterations``, ``setRegParam``, ``setMiniBatchFraction``,
``setConvergenceTol``) with defaults step=1.0, iters=100, frac=1.0, reg=0.0,
convTol=0.001.  Here the same knobs live in a frozen dataclass; the fluent
setters on :class:`~tpu_sgd.optimize.gradient_descent.GradientDescent` return
updated copies of it.

Reference parity: [U] mllib/optimization/GradientDescent.scala (defaults set in
the class constructor; see SURVEY.md §2 #2, §5.6).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple


class Hyper(NamedTuple):
    """The two hyper-parameters every compiled SGD program takes as
    OPERANDS (one small pytree, two scalars): a new value of either runs the
    program that is already built.  Everything else of :class:`SGDConfig` is
    the program's STRUCTURE (:meth:`SGDConfig.structure`)."""

    step_size: Any
    reg_param: Any


@dataclasses.dataclass(frozen=True)
class SGDConfig:
    """Hyper-parameters of mini-batch SGD, with the reference's defaults.

    Attributes:
      step_size: initial step size; decays as ``step_size / sqrt(iter)``
        inside the updaters (parity with Spark's ``Updater.compute``).
      num_iterations: number of outer SGD iterations.
      reg_param: regularization strength handed to the updater.
      mini_batch_fraction: Bernoulli sampling fraction per iteration
        (parity with ``data.sample(false, frac, 42 + i)``).
      convergence_tol: early-exit tolerance on the relative weight delta,
        ``||w_new - w_old|| < tol * max(||w_new||, 1)``.
      seed: base RNG seed; iteration ``i`` folds in ``seed + i`` (the
        distributional analogue of Spark's per-iteration seed ``42 + i``).
      sampling: mini-batch sampling strategy when ``mini_batch_fraction < 1``.
        ``"bernoulli"`` (default) is exact reference parity — a per-example
        Bernoulli mask, normalized by the realized count; it computes the
        full-dataset matvec with masked coefficients.  ``"indexed"`` gathers
        a fixed-size batch of ``round(frac * n)`` rows sampled with
        replacement — distributionally equivalent for SGD, and sound on the
        host-streamed path (a host gather, ``optimize/streamed.py``).  On a
        RESIDENT X the chip read it slower than the masked scan at both
        layouts: at d = 1000 (stored feature-major) ``X[idx]`` has all of X
        copied first, and by rows (2,097,152 x 1,024 bf16, 209,715 drawn
        rows) the gather and its step take 7.709 ms against the masked
        scan's 5.831 (builder's chip runs, PR 26 and PR 39; ROADMAP
        Design 3).  ``"sliced"`` is the HBM-optimal fast path: a
        contiguous row window of ``round(frac * n)`` rows at a per-iteration
        random offset — sequential DMA instead of a random gather (several
        times faster again), read in place: once, by the one-read kernel
        at the window's block offset, on a TPU that stores X feature-major
        (``Gradient.window_sums`` selects it from its operands); twice, by
        two matvecs, everywhere else.  Sliced
        sampling is statistically sound when row order carries no signal
        (shuffled or i.i.d.-generated datasets); shuffle once beforehand if
        your rows are ordered.
    """

    step_size: float = 1.0
    num_iterations: int = 100
    reg_param: float = 0.0
    mini_batch_fraction: float = 1.0
    convergence_tol: float = 0.001
    seed: int = 42
    sampling: str = "bernoulli"

    def __post_init__(self):
        # the same range checks the fluent setters enforce: direct
        # construction and replace() must not smuggle in values that
        # silently train wrong (frac=0 samples empty batches forever)
        if self.sampling not in ("bernoulli", "indexed", "sliced"):
            raise ValueError(
                "sampling must be 'bernoulli', 'indexed' or 'sliced', "
                f"got {self.sampling!r}"
            )
        if not (0.0 < self.mini_batch_fraction <= 1.0):
            raise ValueError(
                "mini_batch_fraction must be in (0, 1], got "
                f"{self.mini_batch_fraction}"
            )
        if self.num_iterations < 1:
            raise ValueError(
                f"num_iterations must be >= 1, got {self.num_iterations}"
            )
        if self.step_size <= 0.0:
            raise ValueError(
                f"step_size must be positive, got {self.step_size}"
            )
        if self.reg_param < 0.0:
            raise ValueError(
                f"reg_param must be >= 0, got {self.reg_param}"
            )
        if not (0.0 <= self.convergence_tol <= 1.0):
            raise ValueError(
                "convergence_tol must be in [0, 1], got "
                f"{self.convergence_tol}"
            )

    def replace(self, **kwargs) -> "SGDConfig":
        return dataclasses.replace(self, **kwargs)

    def hyper(self) -> Hyper:
        """The operands' values, as the Python floats the updaters were
        always handed: a jitted program takes them as weakly typed scalars,
        so the update's arithmetic is what a closed-over float gave."""
        return Hyper(*(float(getattr(self, name)) for name in Hyper._fields))

    def structure(self) -> "SGDConfig":
        """This config with every operand at its default: equal for two
        configs that one compiled program serves."""
        return _structure_of(self)


@functools.lru_cache(maxsize=64)
def _structure_of(config: SGDConfig) -> SGDConfig:
    # asked for on every fit (the memo keys): one dictionary lookup
    return dataclasses.replace(config, **{
        name: getattr(SGDConfig, name) for name in Hyper._fields})


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Shape of the device mesh the optimizer runs over.

    The reference's only parallelism axis is data parallelism (SURVEY.md §2
    parallelism ledger); ``model`` is the optional feature-sharding hook for
    very wide weight vectors (SURVEY.md §2 ledger, TP row).
    """

    data: int = 1
    model: int = 1

    def build(self, devices=None):
        """Materialize the ``jax.sharding.Mesh`` this config describes
        (``devices`` defaults to all visible devices)."""
        from tpu_sgd.parallel.mesh import make_mesh

        return make_mesh(n_data=self.data, n_model=self.model,
                         devices=devices)

    @property
    def n_devices(self) -> int:
        return self.data * self.model


def _default_shed_utilization():
    # interactive deliberately absent: the premium lane sheds only at
    # queue-full-with-no-victim (serve/batcher.py documents the order)
    return {"batch": 0.75, "shadow": 0.50}


@dataclasses.dataclass(frozen=True)
class ServingConfig:
    """Serving-plane admission knobs — the control plane's actuation
    surface (ROADMAP item 1).

    ``shed_utilization`` maps lane -> queue-utilization fraction at
    which NEW arrivals to that lane are shed.  Historically these were
    the ``DEFAULT_SHED_UTILIZATION`` module constants in
    ``serve/batcher.py``, which a controller could only monkey-patch;
    now a batcher built with ``shed_utilization=None`` reads the
    PROCESS config here at construction, and a RUNNING batcher is
    actuated through ``MicroBatcher.set_shed_utilization`` — no
    constant ever needs patching.
    """

    shed_utilization: dict = dataclasses.field(
        default_factory=_default_shed_utilization)

    def __post_init__(self):
        for lane, thr in self.shed_utilization.items():
            if not (0.0 < float(thr) <= 1.0):
                raise ValueError(
                    f"shed_utilization[{lane!r}] must be in (0, 1], "
                    f"got {thr}")

    def replace(self, **kwargs) -> "ServingConfig":
        return dataclasses.replace(self, **kwargs)


_SERVING_CONFIG = ServingConfig()


def serving_config() -> ServingConfig:
    """The process-wide serving config new batchers default to."""
    return _SERVING_CONFIG


def set_serving_config(cfg: ServingConfig) -> ServingConfig:
    """Install a new process-wide serving config (returns the previous
    one, for scoped restore in tests).  Affects batchers constructed
    AFTER the call; running ones are actuated via their own
    ``set_shed_utilization``."""
    global _SERVING_CONFIG
    if not isinstance(cfg, ServingConfig):
        raise TypeError(f"expected ServingConfig, got {type(cfg).__name__}")
    prev = _SERVING_CONFIG
    _SERVING_CONFIG = cfg
    return prev
