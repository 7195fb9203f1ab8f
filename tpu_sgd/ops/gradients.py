"""Loss gradients for generalized linear models, batched TPU-first.

Reference parity: [U] mllib/optimization/Gradient.scala (SURVEY.md §2 #3).
Spark's ``Gradient.compute(data, label, weights) -> (gradient, loss)`` is a
per-example scalar loop over BLAS ``dot``/``axpy`` calls.  On TPU the idiomatic
form is one fused batched matvec pipeline (SURVEY.md §2 native-component
ledger): every linear-model gradient factors as

    margins   = X @ w                      # MXU matvec / matmul
    coeff, l  = pointwise(margins, y)      # VPU elementwise
    grad_sum  = X.T @ (coeff * mask)       # MXU matvec
    loss_sum  = sum(l * mask)

so each Gradient subclass only supplies the ``pointwise`` rule and the whole
mini-batch runs in two MXU passes that XLA fuses with the elementwise ops.
The per-example ``compute`` method is kept for contract parity and testing.

Closed forms mirrored exactly from the reference semantics (SURVEY.md §3.2):
  * LeastSquaresGradient:  diff = x.w - y;  loss = diff^2 / 2;  grad = diff * x
  * LogisticGradient (binary): margin = -x.w;
        multiplier = 1/(1+exp(margin)) - y;  grad = multiplier * x
        loss = log1p(exp(margin))            if y > 0
               log1p(exp(margin)) - margin   otherwise
  * HingeGradient: s = 2y - 1 in {-1, +1};  if 1 - s*(x.w) > 0:
        grad = -s * x, loss = 1 - s*(x.w);  else 0, 0
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from tpu_sgd.config import SGDConfig
from tpu_sgd.ops import pallas_kernels as pk
from tpu_sgd.ops.pallas_kernels import OneRead
from tpu_sgd.ops.sparse import is_sparse as _is_sparse

Array = jax.Array

#: element budget for the multinomial line-search sweep's (n, chunk, K)
#: logit intermediates (~256 MB f32); bounds the activation-memory cost the
#: sequential ladder never paid while keeping X reads far below one-per-trial
SWEEP_BUDGET_ELEMS = 64_000_000


def matmul_dtype(X: Array):
    """The shared mixed-precision contract for every hot-path matmul:
    ``pallas_kernels.operand_dtype`` of the rows' type, its one home (the
    one-read kernels read it there).  Float data in its own dtype with f32
    accumulation, 8-bit integer rows as the bf16 values they exactly are,
    ``bool`` and wider integers in f32."""
    return pk.operand_dtype(X.dtype)


def acc_dtype(mm_dtype):
    """Accumulation dtype paired with :func:`matmul_dtype`: at least f32, but
    never narrower than the inputs — f64 data under ``jax_enable_x64`` keeps
    f64 accumulation instead of being silently downcast to f32."""
    return jnp.promote_types(mm_dtype, jnp.float32)


def margins_of(X, weights):
    """``X @ w`` (or ``X @ Wᵀ`` for matrix trial/class weights) with the
    mixed-precision matmul contract on dense features and the BCOO
    gather/segment-sum lowering on sparse ones.

    Sparse path note: with ~0.1% nnz the matmul FLOPs are negligible, so the
    bf16 HBM-traffic argument doesn't apply — sparse compute runs at the
    accumulation dtype (>= f32; int one-hot data promotes instead of
    truncating the weights)."""
    rhs = weights.T if weights.ndim == 2 else weights
    with jax.named_scope("sgd.margins"):
        if _is_sparse(X):
            cd = acc_dtype(matmul_dtype(X))
            return X.astype(cd) @ rhs.astype(cd)
        mm_dtype = matmul_dtype(X)
        return jnp.dot(
            X.astype(mm_dtype), rhs.astype(mm_dtype),
            preferred_element_type=acc_dtype(mm_dtype),
        )


def grad_sum_of(coeff, X):
    """``coeffᵀ @ X`` (the gradient-sum matvec / matmul), sparse-aware; the
    dense path is written ``coeff @ X`` so it stays row-major friendly."""
    lhs = coeff.T if coeff.ndim == 2 else coeff
    with jax.named_scope("sgd.gradient"):
        if _is_sparse(X):
            cd = acc_dtype(matmul_dtype(X))
            return lhs.astype(cd) @ X.astype(cd)
        mm_dtype = matmul_dtype(X)
        return jnp.dot(
            lhs.astype(mm_dtype), X.astype(mm_dtype),
            preferred_element_type=acc_dtype(mm_dtype),
        )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class RowDraw:
    """A Bernoulli mini-batch mask that is not drawn yet: what a step hands
    ``Gradient.batch_sums`` in the mask's place where the one-read kernel
    draws each row's bit itself (:func:`step_sums`' ``mask_in_kernel``), so
    that no array of the mask is made.  It stands for ``bernoulli(key, fraction,
    (n,)) & valid``, the same rows wherever it is drawn: :meth:`mask` IS
    that array, for every path that is no such kernel."""

    key: Array
    valid: Optional[Array]
    fraction: float = dataclasses.field(metadata=dict(static=True))

    def mask(self, n: int) -> Array:
        with jax.named_scope("sgd.sample"):
            drawn = jax.random.bernoulli(self.key, self.fraction, (n,))
            return drawn if self.valid is None else drawn & self.valid


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class RowCount:
    """The row mask of an array that holds a CAPACITY of rows, the first
    ``rows`` of them real (a stream's micro-batch: its size is data, so that
    no program is compiled for it): what a fit is handed as ``valid`` in the
    place of ``arange(n) < rows``.  Where every step's sums take the
    one-read kernel that bounds its grid by a count
    (``pallas_kernels.OneRead.bounds``) it goes to the kernel as it is, a
    scalar, and the rows past it are neither read nor counted;
    :func:`rows_valid` makes it the array it stands for everywhere else."""

    rows: Array  # int32 scalar

    def mask(self, n: int) -> Array:
        return jnp.arange(n, dtype=jnp.int32) < self.rows


def counter_draws() -> bool:
    """Whether ``jax.random.bernoulli`` under a key that
    ``jax.random.PRNGKey`` makes draws entry i from i alone: threefry2x32
    run as a counter (``jax_threefry_partitionable``; JAX's default since
    0.5), which a kernel can repeat for the rows of its block.  Under
    another implementation (``rbg``) or with the flag off the bits depend
    on the whole array's shape, and the mask stays an array."""
    return bool(jax.config.jax_threefry_partitionable
                and jax.config.jax_default_prng_impl == "threefry2x32")


def one_read_of(X, y, weights, mask=None, margin_axis_name=None,
                classes: Optional[int] = None, window: Optional[int] = None
                ) -> Optional[OneRead]:
    """The fused one-read kernel that ``batch_sums`` of these operands
    takes where the program is lowered for a TPU (``window``:
    ``window_sums`` over a window of so many rows), as the record
    ``pallas_kernels.one_read`` makes of their shape, None where the sums
    take two reads.  Decided from what the operands look like, nothing
    else.  HERE: dense 2-D bf16, f32 or int8 rows (int8 where the chip
    stores them by rows: THERE), a flat weight vector
    (``classes`` None: one entry a feature; a class count: the row-major
    flattening of a ``(classes - 1, d)`` matrix), one label (and one mask
    entry) a row, whole margins on every core (a feature-sharded run needs
    the ``psum`` between the two halves), a window inside the rows.
    THERE, from the shape: whether the kernel can take X's blocks in the
    order the chip stores them, so that no copy of X stands in front of
    it, which body and grid that is, and whether a block fits its VMEM
    beside the weights.  A mask the kernel draws itself (:class:`RowDraw`)
    is no operand: its ``valid`` is."""
    if isinstance(mask, RowDraw):
        mask = mask.valid
    if isinstance(mask, RowCount):  # a scalar: no row operand
        mask = None
    if (margin_axis_name is not None or _is_sparse(X)
            or getattr(X, "ndim", 0) != 2 or jnp.ndim(weights) != 1
            or X.dtype not in (jnp.bfloat16, jnp.float32, jnp.int8)):
        return None
    n, d = X.shape
    if (jnp.shape(y) != (n,)
            or (mask is not None and jnp.shape(mask) != (n,))
            or (window is not None and not 0 < window <= n)):
        return None
    rows = 0
    if classes is not None:
        if jnp.shape(weights) != ((classes - 1) * d,):
            return None
        rows = pk.class_rows_of(classes - 1, X.dtype)
    return pk.one_read(n, d, X.dtype.itemsize, mask is not None, rows,
                       window is not None)


@dataclasses.dataclass(frozen=True)
class StepSums:
    """What every step of a fit hands its sums besides the labels, and the
    kernel they take of it on a TPU: :func:`step_sums`' answer."""

    #: the row mask a step hands on as it stands (a padded shard's
    #: ``valid``), None where there is none or the draw folds it in
    mask: Any
    #: rows of the window under ``sampling="sliced"``, else None
    window: Optional[int]
    #: the step makes its Bernoulli mask anew as an ARRAY, ``valid`` folded
    #: into it
    drawn: bool
    #: the one-read kernel of every step's sums, None where they take two
    #: reads (or the step gathers its rows, ``"indexed"``)
    kernel: Optional[OneRead]
    #: that kernel draws every step's Bernoulli mask itself: the step hands
    #: it a :class:`RowDraw` (``train.run``'s attribute of the same name)
    mask_in_kernel: bool


def window_rows(cfg: SGDConfig, n_rows: int) -> int:
    """Rows of a sliced or indexed mini-batch over ``n_rows`` rows."""
    return max(1, round(cfg.mini_batch_fraction * n_rows))


def rows_valid(gradient: "Gradient", cfg: SGDConfig, X, y, weights, valid,
               model_axis_name=None):
    """``valid`` as a fit's steps take it.  A :class:`RowCount` stays one
    where every step is a full batch whose sums take a one-read kernel that
    bounds its grid by the count (on a TPU; the same program lowered for
    another platform makes the mask in ``Gradient._two_read_default``);
    everywhere else (a sampled fit, a matrix of weights, the wide and
    by-rows bodies, two reads) it is made the ``(n,)`` array it stands for,
    here, once, and the fit is the one a padded shard's ``valid`` gets."""
    if not isinstance(valid, RowCount):
        return valid
    if cfg.mini_batch_fraction >= 1.0:
        kernel = gradient.one_read(X, y, weights, valid, model_axis_name)
        if kernel is not None and kernel.bounds:
            return valid
    return valid.mask(X.shape[0])


def step_sums(gradient: "Gradient", cfg: SGDConfig, X, y, weights,
              valid=None, model_axis_name=None) -> StepSums:
    """What every step of ``make_run``'s fit over these operands (a
    shard's, under a mesh) hands ``gradient``'s sums, and the one-read
    kernel they take of it where the program is lowered for a TPU
    (``Gradient.one_read``).  The ONE asker of the step: the mask
    ``_make_mask`` makes, the rows ``prepare_rows`` lays out before the
    loop, ``train.run``'s attributes and the planner's count of reads all
    read this.  From shapes, types and JAX's configuration alone, so the
    host can ask it of a fit it is about to dispatch.

    A full batch hands on ``valid``; ``"sliced"`` ``valid`` and a window;
    ``"indexed"`` gathers its rows and hands nothing on as it stands.
    ``"bernoulli"`` hands the kernel a :class:`RowDraw` where its body
    draws (``OneRead.draws``) and the draw is a counter's
    (:func:`counter_draws`), ``valid`` an operand of its own; everywhere
    else the step draws the array it always drew."""
    sampled = cfg.mini_batch_fraction < 1.0
    if sampled and cfg.sampling == "indexed":
        return StepSums(None, None, False, None, False)
    window = None
    if sampled and cfg.sampling == "sliced":
        window = window_rows(cfg, X.shape[0])
    kernel = gradient.one_read(X, y, weights, valid, model_axis_name, window)
    if not sampled or window is not None:
        return StepSums(valid, window, False, kernel, False)
    if kernel is not None and kernel.draws and counter_draws():
        return StepSums(valid, None, False, kernel, True)
    drawn = jax.ShapeDtypeStruct((X.shape[0],), bool)  # the step's draw
    return StepSums(None, None, True, gradient.one_read(
        X, y, weights, drawn, model_axis_name), False)


class Gradient:
    """Loss-specific plugin: the ``Gradient`` axis of the optimizer boundary.

    Subclasses implement :meth:`pointwise`; everything else (single-example
    ``compute``, batched ``batch_sums``) derives from it.
    """

    def pointwise(self, margin: Array, label: Array) -> Tuple[Array, Array]:
        """Elementwise rule: ``(dloss/dmargin, loss)`` given ``margin = x.w``."""
        raise NotImplementedError

    def weight_dim(self, num_features: int) -> int:
        """Length of the flat weight vector for ``num_features`` inputs."""
        return num_features

    def compute(self, data: Array, label: Array, weights: Array) -> Tuple[Array, Array]:
        """Single-example ``(gradient, loss)`` — Spark contract parity."""
        margin = jnp.dot(data, weights)
        coeff, loss = self.pointwise(margin, label)
        return coeff * data, loss

    def batch_sums(
        self,
        X: Array,
        y: Array,
        weights: Array,
        mask: Optional[Array] = None,
        margin_axis_name: Optional[str] = None,
        rows=None,
    ) -> Tuple[Array, Array, Array]:
        """Fused mini-batch ``(grad_sum, loss_sum, count)``.

        This is the XLA-compiled replacement for the reference's executor-side
        per-example seqOp loop (SURVEY.md §3.1 inner hot loop): the whole
        shard's contribution in two matvecs (two reads of X) or, where
        :func:`one_read_of` names a kernel and the program is lowered for a
        TPU, in one fused pass.  ``mask`` implements Bernoulli
        mini-batch sampling; sums are *unnormalized* so they can be combined
        across shards with ``lax.psum`` before dividing by the realized
        mini-batch count (parity with ``treeAggregate`` + ``/ miniBatchSize``).

        ``margin_axis_name``: when the FEATURE axis is sharded (wide-weights
        mode), each core computes a partial margin from its column block;
        pass the mesh axis to all-reduce those partials into full margins.
        The returned grad_sum is then the local feature block's gradient.

        ``rows``: what :meth:`row_operands` made of these ``X``, ``y`` and
        (where it is the same every call) this ``mask``, once, in front of
        the caller's loop; the one-read kernel then reads them as they lie.
        They ride beside ``y`` and ``mask``, which the two-read path reads.

        A :class:`RowDraw` in the mask's place (a step hands one on where
        :func:`step_sums` says ``mask_in_kernel``, nowhere else) is drawn by the
        kernel, row by row, and made the array it stands for where the program is
        lowered for another platform.
        """
        kernel = one_read_of(X, y, weights, mask, margin_axis_name)
        if isinstance(mask, RowCount) and not (kernel and kernel.bounds):
            mask = mask.mask(X.shape[0])  # ``rows_valid`` comes before
            kernel = one_read_of(X, y, weights, mask, margin_axis_name)
        if kernel is not None:
            # both are traced; the platform the program is LOWERED for
            # picks one, so a CPU process compiling for the chip gets the
            # kernel and a CPU run the two matvecs it always had
            return jax.lax.platform_dependent(
                X, y, weights, mask, rows,
                tpu=functools.partial(self._fused_sums, kernel),
                default=self._two_read_default)
        return self._two_read_sums(X, y, weights, mask, margin_axis_name)

    def _two_read_default(self, X, y, weights, mask, rows):
        """``platform_dependent``'s other branch: the ``(n,)`` operands,
        the mask drawn as an array."""
        if isinstance(mask, (RowDraw, RowCount)):
            mask = mask.mask(X.shape[0])
        return self._two_read_sums(X, y, weights, mask)

    def one_read(self, X, y, weights, mask=None, margin_axis_name=None,
                 window: Optional[int] = None) -> Optional[OneRead]:
        """The one-read kernel that the sums of every step take of these
        operands where the program is lowered for a TPU (``batch_sums``;
        ``window_sums`` over a window of ``window`` rows), None where the
        step takes two reads (:func:`one_read_of`).  What a fit asks before
        its loop (:func:`step_sums`): whether to lay the labels out for the
        kernel, whether the kernel draws the mask, what ``train.run`` says
        of the step.  A gradient whose steps are no such sums overrides it
        to say so.  From shapes and types alone, so the host can ask it."""
        return one_read_of(X, y, weights, mask, margin_axis_name,
                           window=window)

    def row_operands(self, X, y, weights, valid=None,
                     margin_axis_name=None, window: Optional[int] = None):
        """The kernel's loop-invariant row operands, laid out ONCE: ``(labels,
        valid or None)`` as the ``(1, n)`` float32 rows its block specs
        read (``pallas_kernels.row_operand``), for the caller of a loop
        to make in front of it and hand to every step's ``batch_sums`` /
        ``window_sums`` as ``rows``; None where no kernel will read them
        (:meth:`one_read`) and the step takes ``y`` as it is.

        ``valid`` is a mask that is the same every step (a padded shard's);
        one drawn each step stays the step's.  Why the source and not the
        compiler moves them: ``ops/pallas_kernels.py``, "What reaches the
        kernels as a bitcast"."""
        if self.one_read(X, y, weights, valid, margin_axis_name,
                         window) is None:
            return None
        n = X.shape[0]
        if isinstance(valid, RowCount):  # the kernel's grid is bounded by it
            valid = None
        return (pk.row_operand(y, n),
                None if valid is None else pk.row_operand(valid, n))

    def _fused_sums(self, kernel: OneRead, X, y, weights, mask, rows=None):
        """One read of X: the Pallas kernel ``kernel`` names
        (``ops/pallas_kernels.py``), over the blocks the chip already
        stores, under the record's scope (the entry finds the record's
        tile itself: tests substitute the entries at a tile of theirs)."""
        draw = None
        if isinstance(mask, RowDraw):  # by step_sums the body that draws
            draw, mask = (mask.key, mask.fraction), mask.valid
        y, mask = _kernel_rows(y, mask, rows)
        with jax.named_scope(kernel.scope):
            if isinstance(mask, RowCount):  # by rows_valid the full scan
                return pk.fused_bound_sums(self.pointwise, X, y, weights,
                                           mask.rows)
            if kernel.by_rows:
                return pk.fused_rows_sums(self.pointwise, X, y, weights,
                                          mask)
            if kernel.body == "wide":
                return pk.fused_wide_sums(self.pointwise, X, y, weights,
                                          mask)
            return pk.fused_gradient_sums(self.pointwise, X, y, weights,
                                          mask, draw=draw)

    def _two_read_sums(self, X, y, weights, mask, margin_axis_name=None):
        """Two matvecs, each a pass over all of X (or the BCOO lowering)."""
        margins = margins_of(X, weights)
        if margin_axis_name is not None:
            with jax.named_scope("sgd.margins"):
                margins = jax.lax.psum(margins, margin_axis_name)
        with jax.named_scope("sgd.pointwise"):
            coeff, losses = self.pointwise(margins, y)
            if mask is not None:
                m = mask.astype(margins.dtype)
                coeff = coeff * m
                losses = losses * m
                count = jnp.sum(m)
            else:
                count = jnp.asarray(X.shape[0], margins.dtype)
        grad_sum = grad_sum_of(coeff, X)  # == X.T @ coeff
        with jax.named_scope("sgd.pointwise"):
            loss_sum = jnp.sum(losses)
        return grad_sum, loss_sum, count

    def loss_sweep(
        self,
        X: Array,
        y: Array,
        W: Array,
        mask: Optional[Array] = None,
    ) -> Tuple[Array, Array]:
        """Unnormalized ``(loss_sums (T,), count)`` for T stacked flat trial
        weight vectors ``W`` — the whole line-search backtracking ladder in
        ONE pass that reads X once (``margins = X @ Wᵀ`` is a single MXU
        matmul), instead of T separate matvecs and T host syncs.  Sums are
        per-trial and unnormalized so shards combine with ``lax.psum``
        exactly like :meth:`batch_sums`."""
        margins = margins_of(X, W)  # (n, T)
        _, losses = self.pointwise(margins, y[:, None])
        if mask is not None:
            m = mask.astype(margins.dtype)
            losses = losses * m[:, None]
            count = jnp.sum(m)
        else:
            count = jnp.asarray(X.shape[0], margins.dtype)
        return jnp.sum(losses, axis=0), count

    def window_sums(
        self,
        X: Array,
        y: Array,
        weights: Array,
        start: Array,
        m: int,
        valid: Optional[Array] = None,
        margin_axis_name: Optional[str] = None,
        rows=None,
    ) -> Tuple[Array, Array, Array]:
        """Sums over the contiguous row window ``[start, start + m)`` — the
        ``sampling="sliced"`` mini-batch (SURVEY.md §7 hard parts: the HBM-
        traffic-optimal sampler).  ``start`` is a traced scalar, clamped in
        bounds as ``lax.dynamic_slice`` clamps it.  Two paths, chosen as
        :meth:`batch_sums` chooses, no option: where :func:`one_read_of`
        names a kernel for ``(X, y, weights, valid)`` under a window of
        ``m`` rows and the program is lowered for a TPU, the one-read
        kernel over the window's own blocks of ``X.T``
        at a scalar-prefetched block offset (X read where it lies, the
        window once); everywhere else (a CPU, X stored by rows: the by-rows
        form has no window grid, a feature-sharded run) the slice and two
        matvecs, which read the window in place twice: the compiler fuses
        the slice into each.
        ``batch_sums`` of the sliced rows is NOT the way to one read: it
        would have the window copied out first (compiled for the described
        chip at 4,194,304 x 1000: an 841.5 MB temporary a step).
        ``rows`` as in :meth:`batch_sums` (``row_operands(..., window=m)``).
        """
        if one_read_of(X, y, weights, valid, margin_axis_name,
                       window=m) is not None:
            return jax.lax.platform_dependent(
                X, y, weights, start, valid, rows,
                tpu=lambda X, y, weights, start, valid, rows:
                self._fused_window_sums(X, y, weights, start, valid, m, rows),
                default=lambda X, y, weights, start, valid, rows:
                _window_sums(self._two_read_sums, X, y, weights, start, m,
                             valid, None))
        return _window_sums(self._two_read_sums, X, y, weights, start, m,
                            valid, margin_axis_name)

    def _fused_window_sums(self, X, y, weights, start, valid, m, rows=None):
        """One read of the window, X read in place
        (``ops/pallas_kernels.fused_window_sums``)."""
        y, valid = _kernel_rows(y, valid, rows)
        with jax.named_scope("sgd.fused_sums"):
            return pk.fused_window_sums(self.pointwise, X, y, weights, start,
                                        m, valid)


def _kernel_rows(y, mask, rows):
    """The kernel's ``(labels, mask)``: the rows a fit laid out before its
    loop (``Gradient.row_operands``) where it made them, else the ``(n,)``
    operands, which the kernel's entry lays out itself."""
    if rows is None:
        return y, mask
    return rows[0], mask if rows[1] is None else rows[1]


def _window_sums(sums, X, y, weights, start, m, valid, margin_axis_name):
    """``sums`` (a ``batch_sums``) over the sliced window's rows."""
    if _is_sparse(X):
        raise NotImplementedError(
            "sliced sampling needs a dense row layout; use bernoulli "
            "sampling with sparse (BCOO) features"
        )
    Xb, yb, mask = _slice_window(X, y, valid, start, m)
    return sums(Xb, yb, weights, mask, margin_axis_name=margin_axis_name)


def _slice_window(X, y, valid, start, m):
    """Shared dynamic-slice of a length-``m`` row window (clamped in-bounds,
    matching ``lax.dynamic_slice`` semantics)."""
    Xb = jax.lax.dynamic_slice_in_dim(X, start, m, 0)
    yb = jax.lax.dynamic_slice_in_dim(y, start, m, 0)
    mask = (
        None
        if valid is None
        else jax.lax.dynamic_slice_in_dim(valid, start, m, 0)
    )
    return Xb, yb, mask


class LeastSquaresGradient(Gradient):
    """Squared loss for linear regression: ``L = (x.w - y)^2 / 2``."""

    def pointwise(self, margin: Array, label: Array) -> Tuple[Array, Array]:
        diff = margin - label
        return diff, 0.5 * diff * diff


class LogisticGradient(Gradient):
    """Binary log-loss with labels in {0, 1}, numerically stable.

    Matches the reference's formulation via ``margin = -x.w`` with
    ``log1p(exp(margin))`` saturation guard (SURVEY.md §3.2).  The stable
    rewrite used here is ``softplus(margin) = max(margin, 0) + log1p(exp(-|margin|))``.
    """

    def pointwise(self, margin: Array, label: Array) -> Tuple[Array, Array]:
        neg_margin = -margin  # the reference's "margin" is -x.w
        multiplier = jax.nn.sigmoid(margin) - label  # 1/(1+exp(-x.w)) - y
        softplus = jnp.maximum(neg_margin, 0.0) + jnp.log1p(
            jnp.exp(-jnp.abs(neg_margin))
        )
        loss = jnp.where(label > 0, softplus, softplus - neg_margin)
        return multiplier, loss


class HingeGradient(Gradient):
    """Hinge loss for linear SVM with labels in {0, 1} mapped to {-1, +1}."""

    def pointwise(self, margin: Array, label: Array) -> Tuple[Array, Array]:
        scaled = 2.0 * label - 1.0
        slack = 1.0 - scaled * margin
        active = slack > 0
        coeff = jnp.where(active, -scaled, 0.0)
        loss = jnp.where(active, slack, 0.0)
        return coeff, loss


class MultinomialLogisticGradient(Gradient):
    """K-class logistic gradient over a ``(K-1, D)`` weight matrix.

    Parity with the reference's multinomial branch of ``LogisticGradient``
    ([U] mllib/optimization/Gradient.scala, SURVEY.md §2 #3, "binary +
    multinomial"): the pivot class is class 0, weights hold K-1 rows, and the
    loss is the negative log-likelihood of the softmax with an implicit zero
    logit for the pivot.  The weights travel as the flat vector of
    ``weight_dim`` entries that is the matrix's row-major flattening
    (MLlib's), so every driver, updater and checkpoint holds them as it
    holds a vector's.  One of the ``Gradient`` family: in place of the
    elementwise ``pointwise`` it supplies :meth:`class_rule`, the rule
    between the two products for a whole column of class margins, and
    :meth:`batch_sums` selects between one read of X and two as
    ``Gradient.batch_sums`` does.
    """

    def __init__(self, num_classes: int):
        if num_classes < 2:
            raise ValueError("num_classes must be >= 2")
        self.num_classes = num_classes

    def weight_dim(self, num_features: int) -> int:
        return (self.num_classes - 1) * num_features

    def class_rule(self, margins: Array, labels: Array
                   ) -> Tuple[Array, Array]:
        """THE rule between the two products, for ``(rows, lanes)`` margins
        (row ``r`` is class ``r + 1``; the pivot class 0 has no row and the
        zero logit) and ``(1, lanes)`` labels: ``(dloss/dmargins (rows,
        lanes), loss (1, lanes))``, the softmax written out so that both
        paths trace the same operations.  Rows past ``num_classes - 1`` are
        padding (the kernel holds the class rows at whole registers): they
        take no part in the softmax and get a coefficient of zero."""
        row = jax.lax.broadcasted_iota(jnp.int32, margins.shape, 0)
        real = row < self.num_classes - 1
        logits = jnp.where(real, margins, -jnp.inf)
        top = jnp.maximum(jnp.max(logits, axis=0, keepdims=True), 0.0)
        e = jnp.exp(logits - top)
        total = jnp.exp(-top) + jnp.sum(e, axis=0, keepdims=True)
        onehot = row + 1 == labels.astype(jnp.int32)
        picked = jnp.sum(jnp.where(onehot, margins, 0.0), axis=0,
                         keepdims=True)  # the label's logit; 0 the pivot's
        losses = top + jnp.log(total) - picked
        coeff = e * (1.0 / total) - onehot.astype(margins.dtype)
        return coeff, losses

    def compute(self, data: Array, label: Array, weights: Array
                ) -> Tuple[Array, Array]:
        """Single-example ``(gradient, loss)`` — Spark contract parity; the
        gradient is flat like the weights."""
        grad, loss, _ = self._two_read_sums(
            data[None, :], jnp.reshape(label, (1,)), weights, None)
        return grad, loss

    def batch_sums(
        self,
        X: Array,
        y: Array,
        weights: Array,
        mask: Optional[Array] = None,
        margin_axis_name: Optional[str] = None,
        rows=None,
    ) -> Tuple[Array, Array, Array]:
        """``Gradient.batch_sums`` for the flat ``(K-1) * D`` weights: the
        same selection from the operands and the lowering platform, no
        option.  One read: the class kernel, both products on the matrix
        unit and :meth:`class_rule` between them in VMEM, over blocks of
        ``X.T`` where the chip stores X feature-major and over row blocks
        of X itself where it stores X by rows at a multiple of 128
        (embeddings, hashed spaces, 32 x 32 x 3 pixels: PERF.md, PR 39).
        Two reads: two matmuls with ``(n, K-1)`` margins and coefficients
        in HBM between them (a CPU, a feature-sharded run, BCOO, a by-rows
        width that is no multiple of 128 or overflows the kernel's
        VMEM)."""
        with jax.named_scope("sgd.class_sums"):
            kernel = one_read_of(X, y, weights, mask, margin_axis_name,
                                 classes=self.num_classes)
            if kernel is not None:
                return jax.lax.platform_dependent(
                    X, y, weights, mask, rows,
                    tpu=functools.partial(self._fused_sums, kernel),
                    default=self._two_read_default)
            return self._two_read_sums(X, y, weights, mask, margin_axis_name)

    def one_read(self, X, y, weights, mask=None, margin_axis_name=None,
                 window=None):
        # the class body: no window grid, no draw (``pallas_kernels.one_read``)
        return one_read_of(X, y, weights, mask, margin_axis_name,
                           classes=self.num_classes, window=window)

    def _fused_sums(self, kernel, X, y, weights, mask, rows=None):
        """One read of X (``ops/pallas_kernels.fused_class_sums``, over
        the blocks in the order the chip stores X)."""
        y, mask = _kernel_rows(y, mask, rows)
        W = weights.reshape(self.num_classes - 1, X.shape[-1])
        grad, loss_sum, count = pk.fused_class_sums(
            self.class_rule, X, y, W, mask, by_rows=kernel.by_rows)
        return grad.reshape(-1), loss_sum, count

    def _two_read_sums(self, X, y, weights, mask, margin_axis_name=None):
        """Two matmuls, each a pass over all of X (or the BCOO lowering)."""
        W = weights.reshape(self.num_classes - 1, X.shape[-1])
        # (n, K-1); partial if features are sharded
        margins = margins_of(X, W)
        if margin_axis_name is not None:
            with jax.named_scope("sgd.margins"):
                margins = jax.lax.psum(margins, margin_axis_name)
        with jax.named_scope("sgd.pointwise"):
            coeff, losses = self.class_rule(margins.T, y[None, :])
            if mask is not None:
                m = mask.astype(margins.dtype)
                coeff = coeff * m[None, :]
                losses = losses * m[None, :]
                count = jnp.sum(m)
            else:
                count = jnp.asarray(X.shape[0], margins.dtype)
        grad_sum = grad_sum_of(coeff.T, X).reshape(-1)  # flattened (K-1)*D
        with jax.named_scope("sgd.pointwise"):
            loss_sum = jnp.sum(losses)
        return grad_sum, loss_sum, count

    def loss_sweep(
        self,
        X: Array,
        y: Array,
        W: Array,
        mask: Optional[Array] = None,
    ) -> Tuple[Array, Array]:
        """Matrix-weight line-search sweep: stacked flat ``(K-1)*D`` trial
        weights evaluated through ``X @ (chunk·(K-1), D)ᵀ`` MXU matmuls —
        X is read once per trial CHUNK instead of once per trial, so
        multinomial LBFGS/OWLQN sync with the host once per iteration like
        the vector-weight path (the reference's ``CostFun`` economy, [U]
        mllib/optimization/LBFGS.scala).

        The ``(n, chunk, K)`` logit/log-prob intermediates are the memory
        cost that the sequential ladder never paid; the chunk size bounds
        them to ~256 MB f32 (full ladder in one pass for test-size data,
        a handful of X reads for device-resident slabs — still far fewer
        than the sequential path's one read per trial)."""
        T = W.shape[0]
        K = self.num_classes
        D = X.shape[-1]
        n = X.shape[0]
        chunk = max(1, min(T, int(SWEEP_BUDGET_ELEMS // max(n * K, 1))))
        y_int = y.astype(jnp.int32)
        if mask is not None:
            mvec = mask.astype(jnp.float32)
            count = jnp.sum(mvec)
        else:
            mvec = None
            count = None
        sums = []
        for s in range(0, T, chunk):
            Wc = W[s:s + chunk]
            Tc = Wc.shape[0]
            margins = margins_of(X, Wc.reshape(Tc * (K - 1), D))
            margins = margins.reshape(n, Tc, K - 1)
            if count is None:
                count = jnp.asarray(n, margins.dtype)
            # graftlint: disable=shape-trap -- traced by callers: lbfgs/streamed_costfun jit the sweep, the chunk loop unrolls at trace time
            logits = jnp.concatenate(
                [jnp.zeros((n, Tc, 1), margins.dtype), margins], axis=-1
            )  # (n, Tc, K) with pivot logit 0
            log_probs = jax.nn.log_softmax(logits, axis=-1)
            losses = -jnp.take_along_axis(
                log_probs,
                jnp.broadcast_to(y_int[:, None, None], (n, Tc, 1)),
                axis=-1,
            )[..., 0]  # (n, Tc)
            if mvec is not None:
                losses = losses * mvec.astype(losses.dtype)[:, None]
            sums.append(jnp.sum(losses, axis=0))
        # graftlint: disable=shape-trap -- traced by callers (see sweep note above); eager use is once per ladder config
        return jnp.concatenate(sums), count

    def window_sums(self, X, y, weights, start, m, valid=None,
                    margin_axis_name=None):
        """Same window contract as the vector-weight gradients, on the
        slice and two matmuls everywhere: the class kernel has no window
        grid (``batch_sums`` of the sliced rows would have the window
        copied out in front of the kernel, see ``Gradient.window_sums``)."""
        with jax.named_scope("sgd.class_sums"):
            return _window_sums(self._two_read_sums, X, y, weights, start,
                                m, valid, margin_axis_name)

    def predict_class(self, X: Array, weights: Array) -> Array:
        K = self.num_classes
        W = weights.reshape(K - 1, X.shape[-1])
        return pivot_class_traced(X @ W.T)


def pivot_class_traced(margins: Array) -> Array:
    """Multinomial decision rule (pivot class 0 with an implicit zero
    logit): per-class margins -> predicted class as float32.  The SINGLE
    traced home of the rule — the serving kernels and ``predict_class``
    both call it, so a pivot/tie-breaking change can never diverge
    serving from training-side prediction."""
    # graftlint: disable=shape-trap -- traced by callers, as the name says: the serving kernels and predict_class jit this rule
    logits = jnp.concatenate(
        [jnp.zeros((margins.shape[0], 1), margins.dtype), margins], axis=-1
    )
    return jnp.argmax(logits, axis=-1).astype(jnp.float32)


def pivot_class_host(margins) -> "np.ndarray":
    """Host-numpy twin of :func:`pivot_class_traced` for the bucketed
    dense predict paths, where an eager jnp concat/argmax would compile
    one throwaway program per batch size.  np.argmax and jnp.argmax share
    first-max tie-breaking, so the two variants agree exactly."""
    import numpy as np

    margins = np.asarray(margins)
    logits = np.concatenate(
        [np.zeros((margins.shape[0], 1), margins.dtype), margins], axis=-1
    )
    return np.argmax(logits, axis=-1).astype(np.float32)
