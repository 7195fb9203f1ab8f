#!/usr/bin/env python
"""Micro-benchmark the window-gradient kernel variants on the current device.

The VERDICT-r1 "prove or kill Pallas" sweep: times the XLA sliced paths
against the Pallas fused window kernel at several tile sizes, on whatever
platform JAX resolves (the TPU, or CPU with ``JAX_PLATFORMS=cpu``).
Everything device-side is built inside jit — op-by-op dispatch of
multi-GB arrays is slow and leaves temporaries on the device (see
tpu_sgd/ops/pallas_kernels.py module notes).

Usage:
    python bench_kernels.py [--rows N] [--dim D] [--frac F] [--reps K]
                            [matvec grad ws pallas2048 pallas8192 ...]

With no variant arguments, runs the full default sweep.
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("variants", nargs="*",
                    default=["matvec", "grad", "ws", "pallas1024",
                             "pallas2048", "vpu1024", "vpu2048",
                             "scan8192", "scan32768"],
                    help="which paths to time (pallasN = MXU fused window "
                         "kernel at tile_m N; vpuN = the VPU-reduction "
                         "variant, see fused_window_sums_vpu; tiles over "
                         "the VMEM budget are rejected with a clear error, "
                         "see pallas_kernels._check_tile_vmem)")
    ap.add_argument("--rows", type=int, default=2_998_272)
    ap.add_argument("--dim", type=int, default=1000)
    ap.add_argument("--frac", type=float, default=0.1,
                    help="window size as a fraction of rows")
    ap.add_argument("--reps", type=int, default=10)
    args = ap.parse_args(argv)

    rows, d = args.rows, args.dim
    m = max(1, int(args.frac * rows))
    dev = jax.devices()[0]
    print(f"device: {dev.device_kind} ({dev.platform}); "
          f"rows={rows} d={d} window m={m}", flush=True)

    t0 = time.perf_counter()

    @jax.jit
    def gen():
        kx, ky = jax.random.split(jax.random.PRNGKey(0))
        X = jax.random.normal(kx, (rows, d), jnp.bfloat16)
        y = jax.random.normal(ky, (rows,), jnp.float32)
        return X, y

    X, y = jax.block_until_ready(gen())
    w = jnp.ones((d,), jnp.float32)
    print(f"data ready in {time.perf_counter() - t0:.1f}s", flush=True)

    def timeit(name, fn, *fargs, rows_done=None):
        """Times ``fn`` and reports bandwidth for the rows it ACTUALLY
        processes (the pallas variants floor the window to a tile multiple,
        so crediting them with the full m would inflate their GB/s).

        Reps are CHAINED through a device scalar folded into BOTH the
        weight vector and the window-start index: independent dispatches
        let the async runtime overlap reps and over-report bandwidth by
        orders of magnitude (an early sweep printed 11 TB/s "effective" on
        a chip with <1 TB/s of HBM), and a weights-only chain proved
        insufficient in round 3 — several variants still printed 2-3x the
        chip's physical HBM bandwidth, so the start index (which decides
        WHICH bytes are read) now carries the dependency too.  Numbers
        above the HBM spec remain untrustworthy; a full-loop steady
        state is the authoritative comparison."""
        rows_done = m if rows_done is None else rows_done
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*fargs))
        print(f"{name:28s} compile {time.perf_counter() - t0:5.1f}s",
              flush=True)
        w0, start0, rest = fargs[0], fargs[1], fargs[2:]
        zero = jnp.zeros((), w0.dtype)
        izero = jnp.zeros((), start0.dtype)
        t0 = time.perf_counter()
        for _ in range(args.reps):
            out = fn(w0 + zero, start0 + izero, *rest)
            # 0-valued, but data-dependent on the previous dispatch
            zero = out[0].ravel()[0] * 0.0
            izero = zero.astype(start0.dtype)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / args.reps
        gb = rows_done * d * X.dtype.itemsize / 1e9
        print(f"{name:28s} {dt * 1e3:8.3f} ms for {rows_done} rows "
              f"({gb / dt:6.1f} GB/s eff-1-read)", flush=True)
        return dt, rows_done

    results = {}
    variants = args.variants

    if "matvec" in variants:
        @jax.jit
        def matvec_dyn(w, start, X):
            Xb = jax.lax.dynamic_slice_in_dim(X, start, m, 0)
            return (jnp.dot(Xb, w.astype(X.dtype),
                            preferred_element_type=jnp.float32),)

        results["matvec"] = timeit("matvec dynamic window", matvec_dyn, w,
                                   jnp.int32(1024), X)

    if "grad" in variants:
        @jax.jit
        def grad_dyn(w, start, X, y):
            Xb = jax.lax.dynamic_slice_in_dim(X, start, m, 0)
            yb = jax.lax.dynamic_slice_in_dim(y, start, m, 0)
            r = jnp.dot(Xb, w.astype(X.dtype),
                        preferred_element_type=jnp.float32) - yb
            g = jnp.dot(r.astype(X.dtype), Xb,
                        preferred_element_type=jnp.float32)
            return (g,)

        results["grad"] = timeit("grad 2-matmul dynamic", grad_dyn, w,
                                 jnp.int32(1024), X, y)

    if "ws" in variants:
        from tpu_sgd.ops.gradients import LeastSquaresGradient

        g = LeastSquaresGradient()

        @jax.jit
        def ws(w, start, X, y):
            return g.window_sums(X, y, w, start, m)

        results["ws"] = timeit("Gradient.window_sums (xla)", ws, w,
                               jnp.int32(1024), X, y)

    for v in variants:
        if v.startswith("scan"):
            # One-read chunked schedule at the XLA level (ChunkedGradient):
            # the same traffic shape the pallas kernels target, with the
            # MXU mapping left to the compiler.
            from tpu_sgd.ops.gradients import (ChunkedGradient,
                                               LeastSquaresGradient)

            chunk = int(v[len("scan"):])
            cg = ChunkedGradient(LeastSquaresGradient(), chunk_rows=chunk)

            @jax.jit
            def scan_ws(w, start, X, y, cg=cg):
                return cg.window_sums(X, y, w, start, m)

            results[v] = timeit(f"scan chunk={chunk}", scan_ws, w,
                                jnp.int32(1024), X, y)
            continue
        if v.startswith("pallas") or v.startswith("vpu"):
            kind = "vpu" if v.startswith("vpu") else "pallas"
            tile = int(v[len(kind):])
            if m // tile == 0:
                print(f"{v}: window m={m} < tile {tile}; skipped")
                continue
            from tpu_sgd.ops.gradients import LeastSquaresGradient
            from tpu_sgd.ops.pallas_kernels import (
                fused_window_sums,
                fused_window_sums_vpu,
            )

            g = LeastSquaresGradient()
            nt = m // tile
            kernel = (fused_window_sums_vpu if kind == "vpu"
                      else fused_window_sums)

            def pw(w, start, X, y, tile=tile, nt=nt, kernel=kernel):
                return kernel(g.pointwise, X, y, w, start, nt, tile_m=tile)

            try:
                results[v] = timeit(f"{kind} window tile={tile}", pw, w,
                                    jnp.int32(1), X, y, rows_done=nt * tile)
            except Exception as e:  # keep sweeping past a bad tile size
                print(f"{v} failed ({type(e).__name__}: "
                      f"{str(e).splitlines()[0][:120]}); skipping",
                      flush=True)

    if "ws" in results:
        base_dt, base_rows = results["ws"]
        for k, (dt, rows_done) in results.items():
            if (k.startswith("pallas") or k.startswith("vpu")
                    or k.startswith("scan")):
                # Per-row comparison: the pallas window is floored to a tile
                # multiple, so raw wall-clock would not be apples-to-apples.
                ratio = (base_dt / base_rows) / (dt / rows_done)
                print(f"{k} vs ws (per row): {ratio:.2f}x "
                      f"({'kernel wins' if ratio > 1 else 'xla wins'})")
    return results


if __name__ == "__main__":
    main(sys.argv[1:])
