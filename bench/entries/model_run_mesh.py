"""A fit through the model-level entry point with a data mesh set, as a user
whose rows are NOT cached on the chips calls it:
``<Model>WithSGD(...)`` built once, ``optimizer.set_mesh(data_mesh(devices))``,
then ``run((X, y))`` on HOST arrays on every fit: validation, the planner, the
hand-off of the whole dataset to the chips, the sharded fit with one
all-reduce a step, the weights and the loss history back.

``prepare`` refuses a program whose hand-off lands the dataset on ONE chip
first (by what the program does with a few rows a shard, not by a version):
at the cell's size that is 20 GB bound for a 16 GB chip, and a program that
re-lays a smaller dataset from chip 0 over the mesh would spend the run
copying between chips.  So a program without the mechanism exits at once,
with a reason."""

import jax
import numpy as np

import tpu_sgd


class _Spans:
    """A sink for the program's spans: ``{name: [attributes]}``."""

    def __init__(self):
        self.named = {}

    def emit(self, kind, payload):
        self.named.setdefault(payload.get("name"), []).append(dict(payload))


def refuse_a_hand_off_through_one_chip(mesh, features: int, x_dtype):
    """Eight rows a shard as HOST arrays through the program's own hand-off
    over ``mesh``, by both roads: the placement called directly (every
    shard's buffer on its own device) and a fit of one step (``train.h2d``
    says it wrote to every device of the mesh, ``train.place`` that it then
    moved nothing); or it raises."""
    devices = list(mesh.devices.flat)
    rows = 8 * len(devices)
    X, y = np.zeros((rows, features), x_dtype), np.zeros((rows,), np.float32)
    Xd, yd, valid = tpu_sgd.parallel.shard_dataset(mesh, X, y)
    placed = valid is None and all(
        [s.device for s in a.addressable_shards] == devices
        and a.shape[0] == rows for a in (Xd, yd))
    opt = (tpu_sgd.GradientDescent(tpu_sgd.LeastSquaresGradient(),
                                   tpu_sgd.SimpleUpdater())
           .set_num_iterations(1).set_convergence_tol(0.0).set_mesh(mesh))
    spans = _Spans()
    tpu_sgd.obs.enable_tracing(spans)
    try:
        opt.optimize_with_history((X, y), np.zeros((features,), np.float32))
    finally:
        tpu_sgd.obs.disable_tracing()
    h2d = spans.named.get("train.h2d", [{}])[-1]
    place = spans.named.get("train.place", [{}])[-1]
    if not (placed and h2d.get("shards") == len(devices)
            and place.get("in_place") == 1 and place.get("bytes") == 0):
        raise RuntimeError(
            "this program's hand-off stages a host array on one chip before "
            f"it lays it over the mesh (train.h2d shards={h2d.get('shards')}"
            f", train.place in_place={place.get('in_place')} bytes="
            f"{place.get('bytes')}): the cell's 20 GB cannot take that road; "
            "it runs a program that sends every row block to the chip that "
            "owns it")


def prepare(config: dict, X, y, seed: int):
    """Build the algorithm ONCE, its optimizer over the mesh;
    ``fit() -> (weights, loss history)`` from the host arrays."""
    shards = int(config["as_run"]["data_parallel"])
    mesh = tpu_sgd.data_mesh(jax.devices()[:shards])
    refuse_a_hand_off_through_one_chip(mesh, X.shape[1], X.dtype)
    alg = getattr(tpu_sgd, config["model"])(
        float(config["step_size"]), int(config["num_iterations"]),
        reg_param=float(config["reg_param"]),
        mini_batch_fraction=float(config["mini_batch_fraction"]))
    if type(alg.optimizer.gradient).__name__ != config["gradient"]:
        raise ValueError(
            f"{config['model']} trains {type(alg.optimizer.gradient).__name__}"
            f", the configuration states {config['gradient']}")
    (alg.optimizer.set_updater(getattr(tpu_sgd, config["updater"])())
     .set_sampling(config["sampling"])
     .set_convergence_tol(float(config["convergence_tol"]))
     .set_seed(seed)
     .set_mesh(mesh))
    alg.set_schedule(config["schedule"])

    def fit():
        model = alg.run((X, y))
        return (jax.block_until_ready(model.weights),
                np.asarray(alg.optimizer.loss_history))

    return fit
