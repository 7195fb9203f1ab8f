"""``bench/layers/fused_sums_ms.py`` (PR 26) on traces written by hand, through
the helpers of ``test_benchmark_spans.py``: it reads the one-read kernel's
scope where the step took it (``margins_ms`` and ``gradient_ms`` then read
the little each half keeps outside the kernel), and nothing where the step
took the two matvecs: the windowed cell's step, and PR 26's parent."""

import importlib.util
import os

import pytest

_spec = importlib.util.spec_from_file_location(
    "_benchmark_spans_helpers",
    os.path.join(os.path.dirname(__file__), "test_benchmark_spans.py"))
H = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(H)

checkout = H.checkout  # the fixture: a run's trace in a checkout of its own

K, MASK = "%_fused_gradient_sums.6 = custom-call(X, y, m, w)", \
    "%fusion.8 = fusion(bits)"
#: what each half of the step leaves outside the kernel: w laid along the
#: lanes in front of it, the fold of its lane partials behind it
W_LANES, FOLD = "%broadcast_in_dim.23 = broadcast(w)", \
    "%reduce.26 = reduce(pallas_call.16)"
FUSED = "jit(sgd_run)/while/body/cond/branch_0_fun/sgd.fused_sums/" \
    "jit(_fused_gradient_sums)/"
#: fit 0: a while of 60 ms holding the kernel's 50, the mask's 5, the
#: weights' 1 and the fold's 2; fit 1: the kernel's 20 bare
OPS = [(H.WHILE, 30, 60), (W_LANES, 30.5, 1), (K, 32, 50), (MASK, 82, 5),
       (FOLD, 87, 2), (K, 110, 20)]
TF_OPS = {K: FUSED + "pallas_call:", MASK: FUSED + "convert_element_type:",
          W_LANES: FUSED + "sgd.margins/broadcast_in_dim:",
          FOLD: FUSED + "sgd.gradient/reduce_sum:",
          H.WHILE: "jit(sgd_run)/while:"}


def test_fused_sums_ms_reads_the_kernels_scope(checkout):
    reduced, run = checkout(H._text(ops=OPS, tf_ops=TF_OPS))
    # (50 + 5 + 20) ms over 2 fits of 10 iterations
    assert H._read("fused_sums_ms", reduced, run) == pytest.approx(3.75)
    # the two halves' own readers find what each leaves outside the kernel
    # (the innermost scope names an operation): microseconds, which is why
    # their entries list the windowed cell alone since PR 27
    assert H._read("margins_ms", reduced, run) == pytest.approx(1 / 20)
    assert H._read("gradient_ms", reduced, run) == pytest.approx(2 / 20)
    # the while's own 2 ms are the only time under no scope
    assert H._read("step_unscoped_share", reduced, run) \
        == pytest.approx(100 * 2 / 80)


def test_fused_sums_ms_is_nothing_on_a_two_read_step(checkout):
    """The parent's program, and every step the selection leaves alone."""
    assert H._read("fused_sums_ms", *checkout(H._text())) is None
    bare = [e for e in H.HOST if e[0] == "bench.fit"]
    assert H._read("fused_sums_ms",
                   *checkout(H._text(host=bare, tf_ops={}))) is None
