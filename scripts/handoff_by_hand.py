#!/usr/bin/env python3
"""The meshed hand-off from the host, by hand on the chips (PR 46; no cell of
the benchmark, nothing under ``bench/``): the readings that tell what the
issuing threads of ``gradient_descent._stage_dense`` stand in.

    chiprun --chips 4 --timeout 900 -- python3 scripts/handoff_by_hand.py

On a ``rows-a-device x devices`` by 1000 bf16 host array, Fortran-ordered as
the benchmark's (a tile repeated: no generator, the values do not matter),
each reading three times after one warm-up:

- MESHED: the program's own ``shard_dataset`` to a mesh of 1, 2 and 4
  devices under a live ``train.h2d`` span: GB/s, and the span's counters a
  block (``put_ms``, ``write_ms``, ``free_ms``, ``own_ms``, ``stall_ms``);
- SHAPE: the same bytes to 1 and all devices with ``device_put`` ALONE (no
  write into a destination), a thread a device, 16 blocks in flight: as the
  hand-off's strided row blocks, as FLAT contiguous 1-D blocks (nothing for
  the runtime to re-tile) and as C-ordered row blocks;
- NUMPY: numpy's own copy of the strided blocks, and of contiguous ones, on
  1, 2 and 4 threads: what host memory gives that many readers at once.

One line a reading on stdout (``KIND {json}``), all of them in ``--out``.
``--same-device N`` has N threads send to device 0 (one chip is enough: what
N issuing threads cost without N wires; no MESHED readings then).  On the
CPU ``--rows-a-device 40000`` rehearses it in seconds."""

import argparse
import collections
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

COUNTERS = ("blocks", "shards", "stalls", "stall_ms", "put_ms", "write_ms",
            "free_ms", "own_ms")
FEATURES, REPEAT = 1000, 3


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows-a-device", type=int, default=2_500_000)
    ap.add_argument("--same-device", type=int, default=0)
    ap.add_argument("--out", default="chiprun_out/handoff_by_hand.json")
    args = ap.parse_args()

    import jax
    import ml_dtypes

    import tpu_sgd
    from tpu_sgd.obs.spans import disable_tracing, enable_tracing, span
    from tpu_sgd.optimize import gradient_descent as gd
    from tpu_sgd.parallel import shard_dataset

    devices = jax.devices()
    if args.same_device:
        devices = devices[:1] * args.same_device
    S, local, d = len(devices), args.rows_a_device, FEATURES
    n = S * local
    out = {"device": devices[0].device_kind, "devices": S,
           "rows_a_device": local, "features": d, "cpus": os.cpu_count()}
    repeats = range(REPEAT + 1)  # the first compiles and warms

    def say(kind, row):
        out.setdefault(kind.lower(), []).append(row)
        print(kind, json.dumps(row), flush=True)

    # (d, n) C-ordered bytes, seen as the (n, d) Fortran-ordered array
    t = time.perf_counter()
    base = np.empty((d, n), ml_dtypes.bfloat16)
    tile = np.random.default_rng(7).normal(size=(1 << 20,)).astype(base.dtype)

    def fill(j):
        for a in range(0, n, tile.size):
            base[j, a:a + tile.size] = tile[:min(tile.size, n - a)]

    with ThreadPoolExecutor(16) as pool:
        list(pool.map(fill, range(d)))
    X, y = base.T, np.zeros((n,), np.float32)
    rows = gd._block_rows(X, local)
    block_bytes, count = rows * d * 2, local // rows  # whole blocks a device
    out.update(data_s=time.perf_counter() - t, block_rows=rows)
    print(json.dumps(out), flush=True)

    class Sink:
        records = []

        @classmethod
        def emit(cls, kind, payload):
            cls.records.append(dict(payload))

    for M in [m for m in (1, 2, 4) if m <= S and not args.same_device]:
        mesh = tpu_sgd.data_mesh(devices[:M])
        Xm, ym = X[:M * local], y[:M * local]
        for rep in repeats:
            Sink.records.clear()
            enable_tracing(Sink)
            try:
                t = time.perf_counter()
                with span("train.h2d", bytes=Xm.nbytes + ym.nbytes) as h2d:
                    Xd, yd, _ = shard_dataset(mesh, Xm, ym, h2d)
                in_span = time.perf_counter() - t
                jax.block_until_ready((Xd, yd))
                landed = time.perf_counter() - t
            finally:
                disable_tracing()
            rec = next(r for r in Sink.records if r["name"] == "train.h2d")
            Xd.delete()
            yd.delete()
            say("MESHED", {
                "devices": M, "rep": rep, "in_span_s": in_span,
                "landed_s": landed, "gb_s_span": Xm.nbytes / in_span / 1e9,
                "gb_s_landed": Xm.nbytes / landed / 1e9,
                "put_ms_a_block": rec["put_ms"] / rec["blocks"],
                "write_ms_a_block": rec["write_ms"] / rec["blocks"],
                **{k: rec[k] for k in COUNTERS}})

    def threads(devs, send, label, nbytes):
        """``send(s)`` on a thread a device: ``(put_s, stall_s, in_send_s)``
        each; one SHAPE row a repeat."""
        for rep in repeats:
            t = time.perf_counter()
            with ThreadPoolExecutor(len(devs)) as pool:
                got = list(pool.map(send, range(len(devs))))
            took = time.perf_counter() - t
            put, stall, in_send = (sum(part) * 1e3 for part in zip(*got))
            say("SHAPE", {"what": label, "devices": len(devs), "rep": rep,
                          "s": took, "gb_s": nbytes / took / 1e9,
                          "put_ms_a_block": put / (len(devs) * count),
                          "stall_ms_a_thread": stall / len(devs),
                          "in_send_ms_a_thread": in_send / len(devs)})

    def puts_alone(devs, piece_of):
        def send(s):
            flight = collections.deque()
            put_s = stall_s = 0.0
            entered = time.perf_counter()
            for k in range(count):
                if len(flight) == gd._STAGE_IN_FLIGHT:
                    t = time.perf_counter()
                    flight[0].block_until_ready()
                    stall_s += time.perf_counter() - t
                    flight.popleft().delete()
                piece = piece_of(s, k)
                t = time.perf_counter()
                flight.append(jax.device_put(piece, devs[s]))
                put_s += time.perf_counter() - t
            for block in flight:
                block.block_until_ready()
                block.delete()
            return put_s, stall_s, time.perf_counter() - entered

        return send

    per = rows * d
    flat, c_rows = base.reshape(-1), base.reshape(n, d)  # the same bytes
    for devs in (devices[:1], devices) if S > 1 else (devices,):
        whole = len(devs) * count * block_bytes
        for label, piece_of in (
                ("strided_rows", lambda s, k: X[s * local + k * rows:
                                                s * local + (k + 1) * rows]),
                ("flat_1d", lambda s, k: flat[(s * count + k) * per:
                                              (s * count + k + 1) * per]),
                ("c_ordered_rows", lambda s, k: c_rows[
                    s * local + k * rows:s * local + (k + 1) * rows])):
            threads(devs, puts_alone(devs, piece_of), label, whole)

    def copies(label, copy_block):
        for T in (1, 2, 4):
            def copy(s):
                t = time.perf_counter()
                for k in range(count):
                    copy_block(s % S, k)
                return time.perf_counter() - t

            t = time.perf_counter()
            with ThreadPoolExecutor(T) as pool:
                each = list(pool.map(copy, range(T)))
            took = time.perf_counter() - t
            say("NUMPY", {"what": label, "threads": T, "s": took,
                          "gb_s": T * count * block_bytes / took / 1e9,
                          "ms_a_block": sum(each) * 1e3 / (T * count)})

    def strided(arr):
        return lambda s, k: np.ascontiguousarray(
            arr[s * local + k * rows:s * local + (k + 1) * rows])

    words = flat.view(np.uint16)
    copies("contiguous", lambda s, k: words[(s * count + k) * per:
                                            (s * count + k + 1) * per].copy())
    copies("strided_bf16", strided(X))
    copies("strided_uint16", strided(X.view(np.uint16)))

    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
