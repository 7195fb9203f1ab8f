"""What a dense job's first fit imports, and what ``is_sparse`` answers.

``tpu_sgd.ops.sparse.is_sparse`` is asked some thirty times on the way to any
fit.  It once imported ``jax.experimental.sparse`` to ask, and SciPy behind
it: 0.5 to 0.8 s of a dense job's first fit on the chip's machine (PERF.md,
PR 54).  It now reads ``sys.modules``: a BCOO exists only in a process that
has loaded its package.  Every case here runs in a FRESH interpreter (this
process has long since loaded the package), tiny, on the CPU."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PRELUDE = """
import json, sys
import numpy as np
import jax
import jax.numpy as jnp
import tpu_sgd

def loaded():
    return [n for n in ("jax.experimental.sparse", "scipy") if n in sys.modules]
rng = np.random.default_rng(0)
X = rng.standard_normal((256, 16)).astype(np.float32)
w_true = rng.standard_normal((16,)).astype(np.float32)
y = (X @ w_true > 0).astype(np.float32)
def optimizer(gradient, sampling="bernoulli"):
    return (tpu_sgd.GradientDescent(gradient, tpu_sgd.SquaredL2Updater())
            .set_step_size(0.5).set_num_iterations(8).set_reg_param(0.01)
            .set_mini_batch_fraction(0.5).set_sampling(sampling)
            .set_convergence_tol(0.0).set_seed(42))
def report(losses):
    losses = [float(v) for v in np.asarray(losses).ravel()]
    print("REPORT " + json.dumps({"loaded": loaded(), "losses": losses}))
assert not loaded(), loaded()
"""

#: the dense paths the benchmark's cells take, each its own interpreter
DENSE_FITS = {
    "optimize-vector-bernoulli": """
w, losses = optimizer(tpu_sgd.LogisticGradient()).optimize_with_history(
    (jnp.asarray(X), jnp.asarray(y)), np.zeros((16,), np.float32))
report(losses)
""",
    "optimize-vector-sliced": """
w, losses = optimizer(tpu_sgd.LogisticGradient(), "sliced").optimize_with_history(
    (jnp.asarray(X), jnp.asarray(y)), np.zeros((16,), np.float32))
report(losses)
""",
    "optimize-class-weights": """
labels = jnp.asarray((np.arange(256) % 4).astype(np.float32))
opt = optimizer(tpu_sgd.MultinomialLogisticGradient(4)).set_mini_batch_fraction(1.0)
w, losses = opt.optimize_with_history(
    (jnp.asarray(X), labels), np.zeros((3 * 16,), np.float32))
report(losses)
""",
    "optimize-plain": """
w = optimizer(tpu_sgd.HingeGradient()).optimize(
    (jnp.asarray(X), jnp.asarray(y)), np.zeros((16,), np.float32))
report(np.asarray(w)[:2])
""",
    "model-train-host-array": """
model = tpu_sgd.LogisticRegressionWithSGD.train((X, y), num_iterations=8)
report(np.asarray(model.weights)[:2])
""",
    "model-run-host-array": """
alg = tpu_sgd.LogisticRegressionWithSGD(0.5, 8, reg_param=0.01,
                                        mini_batch_fraction=0.5)
alg.run((X, y))
report(alg.optimizer.loss_history)
""",
    "model-run-data-mesh": """
assert len(jax.devices()) == 4, jax.devices()
alg = tpu_sgd.LinearRegressionWithSGD(0.1, 8, mini_batch_fraction=0.5)
alg.optimizer.set_mesh(tpu_sgd.data_mesh(jax.devices()))
alg.run((X, X @ w_true))
report(alg.optimizer.loss_history)
""",
    "stream-linear-train-on": """
alg = tpu_sgd.StreamingLinearRegressionWithSGD(0.1, 8)
alg.set_initial_weights(np.zeros((16,), np.float32))
targets = X @ w_true
model = alg.train_on((X[a:a + 64], targets[a:a + 64]) for a in range(0, 256, 64))
report(alg.algorithm.optimizer.loss_history)
""",
    "stream-logistic-train-on-uneven": """
alg = tpu_sgd.StreamingLogisticRegressionWithSGD(0.5, 8)
alg.set_initial_weights(np.zeros((16,), np.float32))
edges = (0, 70, 160, 210, 256)
model = alg.train_on((X[a:b], y[a:b]) for a, b in zip(edges, edges[1:]))
report(alg.algorithm.optimizer.loss_history)
""",
}


def _fresh(script: str, devices: int = 4) -> dict:
    """The ``REPORT`` line of ``script`` run in an interpreter of its own."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    done = subprocess.run([sys.executable, "-c", PRELUDE + script], env=env,
                          cwd=REPO, capture_output=True, text=True,
                          timeout=300)
    assert done.returncode == 0, done.stderr[-4000:]
    lines = [ln for ln in done.stdout.splitlines() if ln.startswith("REPORT ")]
    assert len(lines) == 1, done.stdout[-2000:]
    return json.loads(lines[0][len("REPORT "):])


@pytest.mark.parametrize("path", sorted(DENSE_FITS))
def test_a_dense_fit_loads_neither_the_sparse_package_nor_scipy(path):
    said = _fresh(DENSE_FITS[path])
    assert said["loaded"] == []
    assert said["losses"] and all(v == v for v in said["losses"])


def test_a_bcoo_fit_in_a_fresh_interpreter_still_trains_sparse():
    """``tests/test_sparse.py::test_gd_sparse_identical_to_dense``'s pin, in
    a process whose first sparse question comes after the user's own import:
    the sparse run IS the dense run."""
    said = _fresh("""
from tpu_sgd.ops.sparse import is_sparse, sparse_data
Xs, ys, _ = sparse_data(400, 60, nnz_per_row=8, kind="linear", seed=3)
def run(Xin):
    opt = (tpu_sgd.GradientDescent(tpu_sgd.LeastSquaresGradient(),
                                   tpu_sgd.SquaredL2Updater())
           .set_step_size(0.1).set_num_iterations(15).set_reg_param(0.01)
           .set_mini_batch_fraction(0.5).set_seed(9))
    w, hist = opt.optimize_with_history((Xin, jnp.asarray(ys)),
                                        jnp.zeros((60,)))
    return np.asarray(w), np.asarray(hist)
w_s, h_s = run(Xs)
w_d, h_d = run(jnp.asarray(Xs.todense()))
np.testing.assert_allclose(h_s, h_d, rtol=1e-4)
np.testing.assert_allclose(w_s, w_d, rtol=1e-4, atol=1e-5)
print("REPORT " + json.dumps({
    "sparse": bool(is_sparse(Xs)), "dense": bool(is_sparse(Xs.todense())),
    "loaded": loaded(), "losses": [float(v) for v in h_s]}))
""")
    assert said["sparse"] is True and said["dense"] is False
    assert "jax.experimental.sparse" in said["loaded"]
    assert len(said["losses"]) == 15
    assert said["losses"][-1] < said["losses"][0]


ANSWERS = """
import scipy.sparse
from tpu_sgd.ops.gram import GramData
from tpu_sgd.ops.sparse import is_sparse
from tpu_sgd.optimize.gradient_descent import StagedAhead

def dense_inputs():
    z = jnp.zeros((16, 16))
    return {
        "numpy": X,
        "jax": jnp.asarray(X),
        "staged-ahead": StagedAhead(X),
        "gram-data": GramData(None, None, None, None, z, z[0], z[0, 0], 256,
                              logical_shape=(256, 16),
                              logical_dtype=jnp.float32),
        "scipy-csr": scipy.sparse.csr_matrix(X),
    }
before = {k: bool(is_sparse(v)) for k, v in dense_inputs().items()}
asked_without_the_package = "jax.experimental.sparse" not in sys.modules

from jax.experimental.sparse import BCOO
after = {k: bool(is_sparse(v)) for k, v in dense_inputs().items()}
bcoo = BCOO.fromdense(jnp.asarray(X))
after["bcoo"] = bool(is_sparse(bcoo))
seen = []
@jax.jit
def inside(M, D):
    seen.extend([bool(is_sparse(M)), bool(is_sparse(D))])
    return (M @ jnp.ones((16,))).sum() + D.sum()
inside(bcoo, jnp.asarray(X)).block_until_ready()
after["bcoo-under-jit"], after["jax-under-jit"] = seen
print("REPORT " + json.dumps({
    "before": before, "after": after,
    "asked_without_the_package": asked_without_the_package}))
"""

NOT_SPARSE = ("numpy", "jax", "staged-ahead", "gram-data", "scipy-csr")


@pytest.fixture(scope="module")
def answers():
    return _fresh(ANSWERS, devices=1)


def test_is_sparse_asks_without_loading_the_package(answers):
    assert answers["asked_without_the_package"] is True


@pytest.mark.parametrize("when", ["before", "after"])
@pytest.mark.parametrize("kind", NOT_SPARSE)
def test_is_sparse_is_false_of_what_is_no_bcoo(answers, kind, when):
    assert answers[when][kind] is False


@pytest.mark.parametrize("kind,sparse", [("bcoo", True),
                                         ("bcoo-under-jit", True),
                                         ("jax-under-jit", False)])
def test_is_sparse_of_a_bcoo_eagerly_and_under_jit(answers, kind, sparse):
    assert answers["after"][kind] is sparse
