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
  1, 2 and 4 threads: what host memory gives that many readers at once;
- FLAT (PR 49, step 0): the WHOLE candidate hand-off, the array's own
  contiguous runs as flat 1-D pieces WITH the program that writes a piece
  into the donated destination behind each, a thread a device, the pieces in
  flight bounded in bytes: a Fortran-ordered array's column runs of a shard's
  rows in groups of ``group`` features (as one ``device_put`` of the list or a
  call a run; all of a shard's rows or ``piece_rows`` of them a piece; where
  one destination takes every row a group is ONE run), and a C-ordered
  array's row blocks flattened (``c_flat_rows``: what ``_stage_dense`` does
  with a C-ordered array since PR 49).  A sample of every destination is read
  back and held against the host's rows bit for bit (``same_bits``);
- WORDS (the same loop, ``what`` says which; rows of kind FLAT): the
  Fortran-ordered array's pieces as ONE buffer each: its row blocks with the
  2-byte items seen as 32-bit words (``f_word_rows``: what ``_stage_dense``
  does with such an array since PR 49), and 2-D slabs of a group's columns
  as words and as they are (the control);
- WRITE: the write programs ALONE on pieces that have landed, six back to
  back, ms a piece on the host's clock (the dispatch is in it; the device's
  own time is ``stage_ms`` of a traced cell): the three ways to unzip the
  words among them.

One line a reading on stdout (``KIND {json}``), all of them in ``--out``;
``--readings`` takes the kinds to make (all of them by default).
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
    ap.add_argument("--readings",
                    default="meshed,shape,numpy,flat,words,write")
    args = ap.parse_args()
    readings = set(args.readings.split(","))

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

    for M in [m for m in (1, 2, 4) if m <= S and not args.same_device
              and "meshed" in readings]:
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
    for devs in ((devices[:1], devices) if S > 1 else (devices,)) \
            if "shape" in readings else ():
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
    if "numpy" in readings:
        copies("contiguous", lambda s, k: words[(s * count + k) * per:
                                                (s * count + k + 1) * per].copy())
        copies("strided_bf16", strided(X))
        copies("strided_uint16", strided(X.view(np.uint16)))

    # -- step 0 of PR 49: the pieces in other forms WITH their writes --------
    import functools

    import jax.numpy as jnp

    BUDGET = gd._STAGE_IN_FLIGHT * gd._STAGE_BLOCK_BYTES

    @functools.partial(jax.jit, static_argnums=(0, 1))
    def dest_of(shape, dtype):
        return jnp.zeros(shape, dtype)

    @functools.partial(jax.jit, donate_argnums=0, static_argnums=4)
    def write_columns(dest, runs, row, col, columns):
        """``runs``: the flat runs of ``columns`` columns' rows ``row:row +
        r`` (one run a column, or one run for all of them), written at
        ``[row:row + r, col:col + columns]``."""
        group = jnp.concatenate(
            [jax.lax.bitcast_convert_type(run, dest.dtype).reshape(
                columns // len(runs), -1) for run in runs])
        dest = jax.lax.dynamic_update_slice(dest, group.T, (row, col))
        return dest, dest[row, col]

    @functools.partial(jax.jit, donate_argnums=0)
    def write_rows(dest, piece, row):
        block = jax.lax.bitcast_convert_type(piece, dest.dtype).reshape(
            -1, dest.shape[1])
        dest = jax.lax.dynamic_update_slice_in_dim(dest, block, row, axis=0)
        return dest, dest[row, 0]

    def column_pieces(cols, lo, hi, group, piece_rows, one_run, wire):
        """The pieces of rows ``lo:hi`` of ``cols`` (``(d, n)`` C-ordered:
        the Fortran-ordered array's columns): ``(runs, write)``, the host's
        flat runs and the call that writes them once they are on a device."""
        cols = cols.view(wire)
        for a in range(lo, hi, piece_rows or hi - lo):
            b = min(a + (piece_rows or hi - lo), hi)
            for j in range(0, d, group):
                g = min(group, d - j)
                if one_run:  # every row of the array: the group is one run
                    runs = [cols[j:j + g].reshape(-1)]
                else:
                    runs = [cols[c, a:b] for c in range(j, j + g)]
                yield runs, functools.partial(
                    columns_written, row=a - lo, col=j, columns=g)

    def columns_written(dest, on, row, col, columns):
        return write_columns(dest, tuple(on), row, col, columns)

    def row_pieces(by_rows, lo, hi, wire):
        by_rows = by_rows.view(wire)
        for a in range(lo, hi, rows):
            yield (by_rows[a:min(a + rows, hi)].reshape(-1),
                   functools.partial(write_rows, row=a - lo))

    # 16-bit elements as 32-bit WORDS along the rows: what the runtime
    # re-tiles is then 4 bytes wide, and the chip unzips the pairs
    @functools.partial(jax.jit, donate_argnums=0)
    def write_row_words(dest, words, row):
        """``words``: ``(r / 2, d)`` uint32, rows ``2k`` and ``2k + 1`` of a
        column in one word."""
        pairs = jax.lax.bitcast_convert_type(words, dest.dtype)
        block = pairs.transpose(0, 2, 1).reshape(-1, dest.shape[1])
        dest = jax.lax.dynamic_update_slice_in_dim(dest, block, row, axis=0)
        return dest, dest[row, 0]

    @functools.partial(jax.jit, donate_argnums=0)
    def write_row_words_transposed(dest, words, row):
        """The same, the pairs unzipped along the columns' own axis."""
        pairs = jax.lax.bitcast_convert_type(words.T, dest.dtype)
        block = pairs.reshape(pairs.shape[0], -1).T
        dest = jax.lax.dynamic_update_slice_in_dim(dest, block, row, axis=0)
        return dest, dest[row, 0]

    @functools.partial(jax.jit, donate_argnums=0)
    def write_row_words_shifts(dest, words, row):
        """The same, the halves taken by a mask and a shift."""
        halves = [jax.lax.bitcast_convert_type(
            half.astype(jnp.uint16), dest.dtype)
            for half in (words & 0xFFFF, words >> 16)]
        block = jnp.stack(halves, axis=1).reshape(-1, dest.shape[1])
        dest = jax.lax.dynamic_update_slice_in_dim(dest, block, row, axis=0)
        return dest, dest[row, 0]

    @functools.partial(jax.jit, donate_argnums=0)
    def write_slab_words(dest, words, row, col):
        """``words``: ``(g, r / 2)`` uint32, a slab of ``g`` columns."""
        pairs = jax.lax.bitcast_convert_type(words, dest.dtype)
        slab = pairs.reshape(pairs.shape[0], -1)
        dest = jax.lax.dynamic_update_slice(dest, slab.T, (row, col))
        return dest, dest[row, col]

    @functools.partial(jax.jit, donate_argnums=0)
    def write_slab(dest, slab, row, col):
        dest = jax.lax.dynamic_update_slice(dest, slab.T, (row, col))
        return dest, dest[row, col]

    def word_row_pieces(cols, lo, hi):
        """Row blocks of the Fortran-ordered array seen as uint32 words."""
        words = cols.view(np.uint32).T  # (n / 2, d), strides (4, 2 n)
        for a in range(lo, hi, rows):
            yield (words[a // 2:min(a + rows, hi) // 2],
                   functools.partial(write_row_words, row=a - lo))

    def slab_pieces(cols, lo, hi, group, piece_rows, words):
        """Slabs of ``group`` columns' rows, 2-D and strided: one buffer."""
        view = cols.view(np.uint32) if words else cols
        k = 2 if words else 1
        for a in range(lo, hi, piece_rows or hi - lo):
            b = min(a + (piece_rows or hi - lo), hi)
            for j in range(0, d, group):
                yield (view[j:j + group, a // k:b // k], functools.partial(
                    write_slab_words if words else write_slab,
                    row=a - lo, col=j))

    def hand_off(devs, pieces_of, as_list, budget):
        """The candidate loop on a thread a device: a piece's puts, its
        write into the donated destination, its delete; the host waits for
        the oldest write before the bytes in flight would pass ``budget``."""
        def send(s):
            device = devs[s]
            with jax.default_device(device):
                dest = dest_of((local, d), base.dtype)
            flight, held, pieces, puts = collections.deque(), 0, 0, 0
            put_s = write_s = stall_s = 0.0
            entered = time.perf_counter()
            for runs, write in pieces_of(s):
                nbytes = sum(r.nbytes for r in runs) \
                    if isinstance(runs, list) else runs.nbytes
                while flight and held + nbytes > budget:
                    t = time.perf_counter()
                    done, freed = flight.popleft()
                    done.block_until_ready()
                    held -= freed
                    stall_s += time.perf_counter() - t
                t0 = time.perf_counter()
                if as_list or not isinstance(runs, list):
                    on = jax.device_put(runs, device)
                    puts += 1
                else:
                    on = [jax.device_put(r, device) for r in runs]
                    puts += len(runs)
                t1 = time.perf_counter()
                dest, done = write(dest, on)
                t2 = time.perf_counter()
                for r in on if isinstance(on, list) else [on]:
                    r.delete()
                put_s += t1 - t0
                write_s += t2 - t1
                flight.append((done, nbytes))
                held += nbytes
                pieces += 1
            return dest, pieces, puts, (put_s, write_s, stall_s,
                                        time.perf_counter() - entered)

        return send

    def check(dest, host, lo, hi):
        """Rows of the destination against the host's, bit for bit."""
        for a in (0, (hi - lo) // 2, hi - lo - 512):
            got = np.asarray(dest[a:a + 512]).view(np.uint16)
            if not np.array_equal(got,
                                  host[lo + a:lo + a + 512].view(np.uint16)):
                return False
        return True

    def flat_reading(label, devs, pieces_of, as_list=True, budget=BUDGET,
                     host=X, **row):
        nbytes = len(devs) * local * d * 2
        for rep in repeats:
            t = time.perf_counter()
            with ThreadPoolExecutor(len(devs)) as pool:
                got = list(pool.map(hand_off(devs, pieces_of, as_list, budget),
                                    range(len(devs))))
            issued = time.perf_counter() - t
            dests, pieces, puts, spent = zip(*got)
            jax.block_until_ready(dests)
            landed = time.perf_counter() - t
            put, write, stall, in_send = (sum(p) * 1e3 / len(devs)
                                          for p in zip(*spent))
            same = all(check(dest, host, s * local, (s + 1) * local)
                       for s, dest in enumerate(dests)) if rep == 0 else None
            peak = max((dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
                       for dev in devs)
            for dest in dests:
                dest.delete()
            say("FLAT", {"what": label, "devices": len(devs), "rep": rep,
                         "issued_s": issued, "landed_s": landed,
                         "gb_s_issued": nbytes / issued / 1e9,
                         "gb_s_landed": nbytes / landed / 1e9,
                         "pieces_a_device": pieces[0], "puts_a_device": puts[0],
                         "put_ms_a_thread": put, "write_ms_a_thread": write,
                         "stall_ms_a_thread": stall,
                         "in_send_ms_a_thread": in_send, "budget": budget,
                         "as_list": as_list, "same_bits": same,
                         "peak_bytes_in_use": peak, **row})

    u16 = np.uint16
    if "flat" in readings:
        for devs in (devices, devices[:1]) if S > 1 else (devices,):
            M = len(devs)
            # a Fortran-ordered array of M shards' rows: one destination
            # takes EVERY row of its array, so its group is one run
            cols = base if M == S else np.ascontiguousarray(base[:, :M * local])

            def columns(group=16, piece_rows=0, one_run=False, wire=None,
                        cols=cols):
                wire = wire or cols.dtype
                return lambda s: column_pieces(
                    cols, s * local, (s + 1) * local, group, piece_rows,
                    one_run, wire)

            if M == 1:
                flat_reading("f_one_run_16", devs, columns(one_run=True),
                             group=16)
                flat_reading("f_one_run_32", devs,
                             columns(32, one_run=True), group=32)
            # the process's peak only grows: the smaller pieces go first
            flat_reading("f_runs_16_list_rows_2e20", devs,
                         columns(piece_rows=1 << 20), group=16,
                         piece_rows=1 << 20)
            flat_reading("f_runs_16_list_half_budget", devs, columns(),
                         budget=BUDGET // 2, group=16)
            flat_reading("f_runs_16_list", devs, columns(), group=16)
            flat_reading("f_runs_16_calls", devs, columns(), as_list=False,
                         group=16)
            flat_reading("f_runs_16_list_uint16", devs, columns(wire=u16),
                         group=16)
            flat_reading("f_runs_32_list", devs, columns(32), group=32)
            c_cols = cols.reshape(M * local, d)  # the same bytes, C-ordered
            flat_reading("c_flat_rows", devs,
                         lambda s, c=c_cols: row_pieces(
                             c, s * local, (s + 1) * local, c.dtype),
                         host=c_cols)
            del cols, c_cols

    if "words" in readings:
        # the Fortran-ordered array's pieces as ONE buffer each: row blocks
        # of 32-bit words, and 2-D slabs of a group's columns (words, and the
        # 16-bit elements as they are: the control)
        for devs in (devices, devices[:1]) if S > 1 else (devices,):
            def pieces(make, *a):
                return lambda s: make(base, s * local, (s + 1) * local, *a)

            flat_reading("f_word_rows", devs, pieces(word_row_pieces))
            flat_reading("f_word_slabs_16_rows_2e20", devs,
                         pieces(slab_pieces, 16, 1 << 20, True))
            flat_reading("f_word_slabs_16", devs,
                         pieces(slab_pieces, 16, 0, True))
            flat_reading("f_slabs_16", devs,
                         pieces(slab_pieces, 16, 0, False))

    if "write" in readings:
        # the write programs alone, on pieces that have landed
        device, K = devices[0], 6
        with jax.default_device(device):
            dest = dest_of((local, d), base.dtype)
        words = base.view(np.uint32)
        kinds = (
            ("columns_16_runs", lambda k: [base[c, :local] for c in
                                           range(16 * k, 16 * k + 16)],
             lambda k: functools.partial(columns_written, row=0, col=16 * k,
                                         columns=16)),
            ("flat_rows", lambda k: base.reshape(-1)[k * per:(k + 1) * per],
             lambda k: functools.partial(write_rows, row=k * rows)),
            ("word_rows", lambda k: words.T[k * rows // 2:(k + 1) * rows // 2],
             lambda k: functools.partial(write_row_words, row=k * rows)),
            ("word_rows_transposed",
             lambda k: words.T[k * rows // 2:(k + 1) * rows // 2],
             lambda k: functools.partial(write_row_words_transposed,
                                         row=k * rows)),
            ("word_rows_shifts",
             lambda k: words.T[k * rows // 2:(k + 1) * rows // 2],
             lambda k: functools.partial(write_row_words_shifts,
                                         row=k * rows)),
            ("strided_rows", lambda k: X[k * rows:(k + 1) * rows],
             lambda k: functools.partial(write_rows, row=k * rows)),
            ("word_slab_16", lambda k: words[16 * k:16 * k + 16, :local // 2],
             lambda k: functools.partial(write_slab_words, row=0,
                                         col=16 * k)),
            ("word_slab_16_rows_2e20",
             lambda k: words[16 * k:16 * k + 16, :min(1 << 20, local) // 2],
             lambda k: functools.partial(write_slab_words, row=0,
                                         col=16 * k)),
            ("slab_16", lambda k: base[16 * k:16 * k + 16, :local],
             lambda k: functools.partial(write_slab, row=0, col=16 * k)))
        for label, piece_of, write_of in kinds:
            for rep in repeats:
                on = [jax.device_put(piece_of(k), device) for k in range(K)]
                jax.block_until_ready(on)
                t = time.perf_counter()
                for k, piece in enumerate(on):
                    dest, done = write_of(k)(dest, piece)
                done.block_until_ready()
                took = time.perf_counter() - t
                nbytes = sum(r.nbytes for r in jax.tree.leaves(on)) / K
                say("WRITE", {"what": label, "rep": rep,
                              "ms_a_piece": took * 1e3 / K,
                              "piece_bytes": nbytes,
                              "gb_s": nbytes / (took / K) / 1e9})
                for r in jax.tree.leaves(on):
                    r.delete()
        dest.delete()

    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
