"""Device-mesh construction helpers.

The reference's distribution substrate is Spark executors + Netty RPC
(SURVEY.md §1 L1-L2); the TPU-native substrate is a ``jax.sharding.Mesh``
whose collectives ride ICI within a slice and DCN across hosts
(SURVEY.md §5.8).  The canonical mesh for this framework is 1-D over the
example axis (``'data'``), with an optional second ``'model'`` axis for
feature sharding of very wide weight vectors (SURVEY.md §2 parallelism
ledger).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec

DATA_AXIS = "data"
MODEL_AXIS = "model"


def superchunk_specs():
    """PartitionSpecs of one fused K-step *superchunk* ``(Xs, ys,
    valids)`` with shapes ``(K, rows, d)`` / ``(K, rows)`` / ``(K,
    rows)``: the STEP axis is replicated (every shard runs all K fused
    steps), the ROW axis shards over 'data'.  THE one definition shared
    by the meshed superstep builder (``parallel/data_parallel.py``) and
    the streamed feed's superchunk transfer (``optimize/streamed.py``),
    so the program's in_specs and the host-side ``device_put`` sharding
    cannot drift."""
    P = PartitionSpec
    return (P(None, DATA_AXIS, None), P(None, DATA_AXIS),
            P(None, DATA_AXIS))


def make_mesh(
    n_data: Optional[int] = None,
    n_model: int = 1,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """Build a ``(data, model)`` mesh; defaults to all devices on 'data'."""
    if devices is None:
        devices = jax.devices()
    if n_data is None:
        n_data = len(devices) // n_model
    n = n_data * n_model
    if n > len(devices):
        raise ValueError(
            f"mesh {n_data}x{n_model} needs {n} devices, have {len(devices)}"
        )
    grid = np.asarray(devices[:n]).reshape(n_data, n_model)
    return Mesh(grid, (DATA_AXIS, MODEL_AXIS))


def data_mesh(devices: Optional[Sequence] = None) -> Mesh:
    """1-D mesh over all devices on the 'data' axis."""
    if devices is None:
        devices = jax.devices()
    return Mesh(np.asarray(devices), (DATA_AXIS,))


def has_model_axis(mesh) -> bool:
    """True when the mesh shards the FEATURE axis (a 2-D (data, model)
    mesh with a non-trivial 'model' dimension) — the one predicate every
    mesh-kind routing decision shares."""
    return mesh is not None and dict(mesh.shape).get(MODEL_AXIS, 1) > 1


def as_data_mesh(mesh):
    """The 1-D data view of a mesh: a data-only mesh passes through;
    TRIVIAL (size-1) extra axes are flattened away — the canonical
    ``make_mesh``/``MeshConfig`` shape is 2-D with ``model=1``, and the
    data-only builders must accept it rather than raise; a genuinely
    sharded extra axis raises the builders' NotImplementedError."""
    if mesh is None or set(mesh.shape) == {DATA_AXIS}:
        return mesh
    extra = {k: v for k, v in dict(mesh.shape).items() if k != DATA_AXIS}
    if DATA_AXIS in dict(mesh.shape) and all(v == 1 for v in extra.values()):
        import numpy as np
        from jax.sharding import Mesh

        return Mesh(np.asarray(mesh.devices).reshape(-1), (DATA_AXIS,))
    raise NotImplementedError(
        f"this operation composes with a 1-D '{DATA_AXIS}' mesh; "
        f"got axes {tuple(mesh.shape)}"
    )


def shard_map_fn(mesh, fn, in_specs, out_specs, check_vma=False):
    """``jax.shard_map`` with this package's default (no replication
    check: the psums inside ``make_step`` are what replicate)."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)
