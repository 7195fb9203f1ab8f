"""A fit at the ``Optimizer`` plugin boundary over a data mesh:
``GradientDescent(...).set_mesh(data_mesh(devices)).optimize_with_history((X,
y), w0)`` on arrays that already lie sharded by rows on those devices (Spark's
``runMiniBatchSGD`` on an RDD cached across executors).  No copy, no planner:
every step sums its chip's rows and all-reduces the sums.

``prepare`` refuses a program whose placement MOVES a dataset that lies
sharded for the mesh (by what the placement does to a few rows laid out as
the dataset is, not by a version): such a program fetches all of X to the
host and sends it back on every fit, 20 GB each way at the cell's size, and a
run of it would be minutes of copying around a second of work."""

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

import tpu_sgd


def _buffers(array):
    return [s.data.unsafe_buffer_pointer() for s in array.addressable_shards]


def refuse_a_placement_that_moves(mesh, features: int, x_dtype):
    """Eight rows a shard, laid out by rows over ``mesh``, through the
    program's one placement function: the buffers come back or it raises."""
    rows = 8 * mesh.devices.size
    by_rows = NamedSharding(mesh, PartitionSpec(mesh.axis_names[0]))
    Xp = jax.device_put(np.zeros((rows, features), x_dtype), by_rows)
    yp = jax.device_put(np.zeros((rows,), np.float32), by_rows)
    Xd, yd, valid = tpu_sgd.parallel.shard_dataset(mesh, Xp, yp)
    if (valid is not None or _buffers(Xd) != _buffers(Xp)
            or _buffers(yd) != _buffers(yp)):
        raise RuntimeError(
            "this program's shard_dataset moves a dataset that already lies "
            "sharded on its mesh: every fit would fetch X to the host and "
            "send it back; the cell runs a program that trains it in place")


def prepare(config: dict, X, y, seed: int):
    """Build the optimizer ONCE; ``fit() -> (weights, loss history)`` runs
    it again on the same sharded arrays, done when both are in hand."""
    shards = int(config["as_run"]["data_parallel"])
    mesh = tpu_sgd.data_mesh(jax.devices()[:shards])
    refuse_a_placement_that_moves(mesh, X.shape[1], X.dtype)
    opt = (tpu_sgd.GradientDescent(getattr(tpu_sgd, config["gradient"])(),
                                   getattr(tpu_sgd, config["updater"])())
           .set_step_size(float(config["step_size"]))
           .set_num_iterations(int(config["num_iterations"]))
           .set_reg_param(float(config["reg_param"]))
           .set_mini_batch_fraction(float(config["mini_batch_fraction"]))
           .set_sampling(config["sampling"])
           .set_convergence_tol(float(config["convergence_tol"]))
           .set_seed(seed)
           .set_mesh(mesh))
    w0 = np.zeros((X.shape[1],), np.float32)

    def fit():
        w, losses = opt.optimize_with_history((X, y), w0)
        return jax.block_until_ready(w), np.asarray(losses)

    return fit
