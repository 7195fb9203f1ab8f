"""``fit_steady_state`` — the fixed-cost/slope fit the CPU benches
(``bench_superstep.py``, ``bench_resident.py``) report their per-iteration
dispatch tax with.  Host arithmetic only."""

import importlib.util
import os

import numpy as np
import pytest

_PATH = os.path.join(os.path.dirname(__file__), "..", "bench_superstep.py")


@pytest.fixture(scope="module")
def fit_steady_state():
    """Import bench_superstep.py without running main() — and without
    keeping the process-wide env it sets for its own runs."""
    saved = {k: os.environ.get(k) for k in ("JAX_PLATFORMS", "XLA_FLAGS")}
    spec = importlib.util.spec_from_file_location("bench_superstep_module",
                                                  _PATH)
    mod = importlib.util.module_from_spec(spec)
    try:
        spec.loader.exec_module(mod)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return mod.fit_steady_state


def test_fit_steady_state_recovers_line(fit_steady_state):
    """Exact linear points recover (slope, fixed) with ~zero residuals."""
    slope, fixed, fit = fit_steady_state(
        [(100, 0.065 + 100 * 2e-5), (300, 0.065 + 300 * 2e-5),
         (1200, 0.065 + 1200 * 2e-5)])
    assert slope == pytest.approx(2e-5, rel=1e-9)
    assert fixed == pytest.approx(0.065, rel=1e-9)
    assert all(abs(r) < 1e-6 for r in fit["residual_ms"])
    assert fit["slope_rel_err"] == pytest.approx(0.0, abs=1e-6)


def test_fit_steady_state_jitter_residuals_and_error(fit_steady_state):
    """Launch jitter shows up in the residuals and the slope error bar."""
    rng = np.random.default_rng(0)
    its = [1200, 3600, 14400]
    true_slope, true_fixed, jitter = 2.5e-5, 0.065, 0.015
    pts = [(i, true_fixed + true_slope * i + jitter * rng.normal())
           for i in its]
    slope, fixed, fit = fit_steady_state(pts)
    # legs are long enough that the slope survives 15 ms of jitter
    assert slope == pytest.approx(true_slope, rel=0.15)
    assert len(fit["residual_ms"]) == 3
    assert fit["slope_rel_err"] is not None and fit["slope_rel_err"] < 0.15


def test_fit_steady_state_nonpositive_slope_fallback(fit_steady_state):
    """A jitter-inverted fit (short legs, noisy host) falls back to the
    longest run's mean instead of reporting a negative rate."""
    slope, fixed, fit = fit_steady_state([(30, 0.5), (120, 0.4)])
    assert slope == pytest.approx(0.4 / 120)
    assert fixed == 0.0
    assert "fallback" in fit


def test_fit_steady_state_two_points_is_the_line_through_them(
        fit_steady_state):
    """With exactly two points the regression is the line through them,
    and no error bar is claimed."""
    slope, fixed, fit = fit_steady_state([(30, 0.1), (120, 0.25)])
    assert slope == pytest.approx((0.25 - 0.1) / 90)
    assert fixed == pytest.approx(0.1 - slope * 30)
    assert "slope_rel_err" not in fit
