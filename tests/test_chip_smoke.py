"""``chip_smoke.py`` rehearsed on the CPU: every phase function called at a
tiny size (the phases take their sizes as arguments — the script has no
size option), the four-chip comparison on 4 of the 8 virtual devices, and
the script itself, which must FAIL here: no TPU, no ``"ok": true``."""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _jsonable(record):
    assert json.loads(json.dumps(record)) == record
    return record


@pytest.fixture(scope="module")
def dense():
    return chip_smoke.phase_dense(16_384, 64, 20, seed=0)


@pytest.mark.parametrize("index,name", [(0, "dense.least_squares"),
                                        (1, "dense.logistic_l2")])
def test_phase_dense(dense, index, name):
    record = _jsonable(dense[0][index])
    assert record["phase"] == name
    assert record["X"] == [16_384, 64] and record["X_dtype"] == "bfloat16"
    assert record["loss_last"] < 0.5 * record["loss_first"]
    assert record["first_call_s"] > 0 and record["repeat_call_s"] > 0
    assert record["outputs_on"] == [str(jax.devices()[0])]


def test_phase_dense_checks_the_generators_truth():
    with pytest.raises(AssertionError, match="generator's truth"):
        chip_smoke.phase_dense(16_384, 64, 20, seed=0, weight_tol=1e-9)


def test_phase_host_streamed():
    record = _jsonable(chip_smoke.phase_host_streamed(8192, 64, 3, 8, seed=0))
    assert record["iterations"] == 24 and record["superstep_k"] == 8
    assert record["wire_dtype"] == "bfloat16"
    assert record["gather"] in ("native", "python")
    assert record["feed_gb_per_s"] > 0
    assert record["loss_last"] < 0.5 * record["loss_first"]


def test_phase_sparse_keeps_bcoo():
    record = _jsonable(chip_smoke.phase_sparse(4096, 2000, 20, 20, seed=0))
    assert record["X"] == [4096, 2000] and record["nse"] == 4096 * 20
    assert record["loss_last"] < record["loss_first"]
    assert record["train_accuracy"] > 0.6


def test_rcv1_like_rows_are_sorted_unique_unit_norm():
    X, y, w = chip_smoke.make_rcv1_like(512, 3000, 20, seed=1)
    cols = np.asarray(X.indices)[:, 1].reshape(512, 20)
    assert (np.diff(cols, axis=1) > 0).all()  # sorted, no duplicates
    assert cols.min() >= 0 and cols.max() < 3000
    norms = np.linalg.norm(np.asarray(X.data).reshape(512, 20), axis=1)
    np.testing.assert_allclose(norms, 1.0, rtol=1e-5)
    assert set(np.unique(y)) == {0.0, 1.0} and 0.4 < y.mean() < 0.6
    # Zipf popularity: low column ids are drawn far more often
    assert np.median(cols) < 3000 / 4


def test_phase_serve_equals_predict(dense):
    record = _jsonable(chip_smoke.phase_serve(dense[1], 64, seed=0))
    assert record["requests"] == 64 and record["d"] == 64
    assert record["max_abs_diff_vs_predict"] <= 1e-3 * record["margin_scale"]


def test_phase_serve_fails_on_a_wrong_answer(dense, monkeypatch):
    from tpu_sgd.serve.engine import PredictEngine

    honest = PredictEngine.predict_batch
    monkeypatch.setattr(PredictEngine, "predict_batch",
                        lambda self, model, X: honest(self, model, X) + 1.0)
    with pytest.raises(AssertionError, match="differ from model.predict"):
        chip_smoke.phase_serve(dense[1], 8, seed=0)


def test_phase_planner_refuses_the_fallback_budget():
    """The CPU backend reports no memory_stats, so the planner answers
    from its fallback — which the smoke treats as a failure."""
    with pytest.raises(AssertionError, match="fallback"):
        chip_smoke.phase_planner()


def test_phase_data_parallel_on_4_virtual_devices():
    record = _jsonable(chip_smoke.phase_data_parallel(
        8192, 64, 5, seed=0, devices=jax.devices()[:4]))
    assert record["devices"] == 4
    assert len({dev for dev, _ in record["shards"]}) == 4
    assert all(shape == [2048, 64] for _, shape in record["shards"])
    assert record["all_reduce_in_compiled_text"] is True
    assert record["max_rel_weight_diff"] <= record["weight_tol"]


def test_phase_data_parallel_fails_when_weights_disagree():
    with pytest.raises(AssertionError, match="weights differ"):
        chip_smoke.phase_data_parallel(8192, 64, 5, seed=0,
                                       devices=jax.devices()[:4],
                                       weight_tol=0.0)


# -- the script itself --------------------------------------------------------

def _run_script(tmp_path, extra_env, *args):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "XLA_FLAGS")}
    env.update({"JAX_PLATFORMS": "cpu", "HOME": str(tmp_path),
                "TMPDIR": str(tmp_path), **extra_env})
    proc = subprocess.run([sys.executable, SCRIPT, *args], env=env,
                          cwd=str(tmp_path), capture_output=True, text=True,
                          timeout=300)
    lines = [json.loads(l) for l in proc.stdout.splitlines() if l.strip()]
    return proc, lines


@pytest.mark.parametrize("args", [(), ("--chips", "4")],
                         ids=["one_chip", "four_chips"])
def test_script_fails_without_a_tpu(tmp_path, args):
    proc, lines = _run_script(tmp_path, {}, *args)
    assert proc.returncode != 0
    assert lines[-1]["ok"] is False
    assert lines[-1]["device"]["platform"] == "cpu"
    assert '"ok": true' not in proc.stdout
    assert not any(str(l.get("phase", "")).startswith(("dense", "sparse"))
                   for l in lines)  # no phase ran


def test_script_cache_dir_is_fixed_under_the_checkout(tmp_path):
    """Without ``JAX_COMPILATION_CACHE_DIR``: ``<checkout>/.jax_cache``,
    the same on two successive starts (the path is part of the key)."""
    dirs = []
    for _ in range(2):
        _, lines = _run_script(tmp_path, {})
        assert lines[0]["phase"] == "compile_cache"
        assert lines[0]["set_in_code"] is True
        dirs.append(lines[0]["dir"])
    assert dirs == [os.path.join(REPO, ".jax_cache")] * 2


def test_script_cache_dir_placed_from_outside(tmp_path, monkeypatch):
    """With the variable set the script sets no directory in code."""
    outside = str(tmp_path / "cache_from_outside")
    _, lines = _run_script(tmp_path, {"JAX_COMPILATION_CACHE_DIR": outside})
    assert lines[0] == {"phase": "compile_cache", "dir": outside,
                        "set_in_code": False}

    calls = []
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
    monkeypatch.setattr(jax.config, "update",
                        lambda *a, **k: calls.append(a))
    assert chip_smoke.configure_compile_cache()["set_in_code"] is False
    assert calls == []
