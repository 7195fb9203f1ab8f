#!/usr/bin/env python
"""End-to-end sparse workflow at real-RCV1 width — never densified.

    python examples/sparse_rcv1.py [--rows N] [--folds K]

Demonstrates the full sparse surface (SURVEY.md §2 #10; [U]
mllib/linalg/Vectors.scala SparseVector training):

  1. RCV1-shaped data at the REAL 47,236-feature width (Zipf feature
     frequencies, unit-norm tfidf-like rows) as a BCOO matrix — densifying
     it would need ``rows x 47,236 x 4`` bytes (18.8 GB at 100k rows);
  2. linear SVM (hinge + L1) trained UNDENSIFIED, sharded over the data
     mesh with one gradient all-reduce per iteration;
  3. k-fold cross-validation straight on the sparse matrix.

On a machine without the TPU attached run with JAX_PLATFORMS=cpu and
XLA_FLAGS=--xla_force_host_platform_device_count=8.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

from tpu_sgd import L1Updater, SVMWithSGD, data_mesh  # noqa: E402
from tpu_sgd.utils.mlutils import k_fold, rcv1_like_data  # noqa: E402

D = 47_236  # the real rcv1.binary feature count


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--rows", type=int, default=30_000)
    ap.add_argument("--folds", type=int, default=3)
    args = ap.parse_args(argv)

    t0 = time.perf_counter()
    X, y, _ = rcv1_like_data(args.rows, d=D, seed=0)
    dense_gb = args.rows * D * 4 / 1e9
    sparse_mb = (X.data.nbytes + X.indices.nbytes) / 1e6
    print(f"data: {args.rows} x {D}, nse={X.nse} "
          f"({sparse_mb:.0f} MB sparse vs {dense_gb:.1f} GB densified) "
          f"[{time.perf_counter() - t0:.1f}s]")

    mesh = data_mesh()
    t0 = time.perf_counter()
    model = SVMWithSGD.train(
        (X, y), num_iterations=60, step_size=100.0, reg_param=1e-5,
        updater=L1Updater(), mesh=mesh,
    )
    acc = float(np.mean(np.asarray(model.predict(X)) == np.asarray(y)))
    nz = int(np.sum(np.asarray(model.weights) != 0))
    print(f"train: {dict(mesh.shape)}-way mesh, acc={acc:.4f}, "
          f"{nz}/{D} nonzero weights [{time.perf_counter() - t0:.1f}s]")

    t0 = time.perf_counter()
    accs = []
    for (Xtr, ytr), (Xva, yva) in k_fold(X, np.asarray(y), args.folds,
                                         seed=1):
        m = SVMWithSGD.train(
            (Xtr, ytr), num_iterations=40, step_size=100.0, reg_param=1e-5,
            updater=L1Updater(), mesh=mesh,
        )
        accs.append(float(np.mean(np.asarray(m.predict(Xva)) == yva)))
    print(f"{args.folds}-fold CV (sparse splits): "
          f"val acc {np.mean(accs):.4f} +/- {np.std(accs):.4f} "
          f"[{time.perf_counter() - t0:.1f}s]")


if __name__ == "__main__":
    main()
