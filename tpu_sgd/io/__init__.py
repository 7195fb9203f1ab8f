"""Shared host→device ingestion layer.

Every streaming path in this codebase ultimately does the same three
things: cut a host-resident dataset into chunks, move each chunk to a
device, and hand it to a compiled consumer.  Before this package each
path hand-rolled that loop — synchronous full-width ``device_put`` with
zero transfer/compute overlap, and a differently-shaped tail chunk that
recompiled the per-chunk kernels (the eager-op shape-compile trap).
The round-5 hardware capture put the cost on the record: the streamed
statistics build was feed-bound at ``build_s=248.2 s`` while the compute
side idled at 0.024 ms/iter.

Three pieces, composed by the streaming consumers (``ops/gram.py``
builders, ``parallel/gram_parallel.py`` meshed builders,
``optimize/streamed.py`` host-streamed SGD):

* :mod:`tpu_sgd.io.chunking` — a chunk planner that emits FIXED-SHAPE
  chunks; the tail is padded in host numpy so the device-side consumer
  compiles exactly one body program (MLlib keeps the pipeline full
  between stages, arXiv:1505.06807 — our stage boundary is the host
  link).
* :mod:`tpu_sgd.io.prefetch` — a bounded-lookahead background producer:
  chunk ``k+1``'s host assembly + ``device_put`` runs on a worker
  thread while chunk ``k``'s kernel executes.  ``depth=2`` is the
  classic double buffer (one chunk being consumed + one in flight), so
  the staging footprint is ~2× one chunk — size ``batch_rows``
  accordingly (``plan.choose_streamed_build`` does).
* :mod:`tpu_sgd.io.wire` — an opt-in bf16 wire format: cast on host,
  transfer half the bytes, upcast/accumulate in f32 on device (the
  SparCML shrink-bytes-on-the-wire move, arXiv:1802.08021, applied to
  the host→HBM hop).
* :mod:`tpu_sgd.io.sparse_wire` — the compressed sparse wire: top-k +
  error-feedback ``(indices, values)`` segments for update-shaped data
  (``wire_compress="topk:<frac>"``; the dropped mass is carried, never
  lost) and fixed-nse BCOO chunk staging for the host-streamed sparse
  feed — see README "Compressed wire".

The superstep executor (``GradientDescent.set_superstep``; README
"Fused stepping") composes with all three: ``stack_superchunk``
(:mod:`tpu_sgd.io.chunking`) bundles K per-iteration batches into one
fixed-shape *superchunk* on the prefetch worker, so both the transfer
count AND the program-dispatch count drop K-fold — the AdaBatch
aggregation lever (arXiv:1711.01761) applied to the dispatch tax.

See README "Ingestion pipeline" for when the bf16 wire is safe and how
``batch_rows`` interacts with the double buffer's 2× staging footprint.
"""

from tpu_sgd.io.chunking import (Chunk, ChunkPlan, pad_rows, plan_chunks,
                                 stack_superchunk)
from tpu_sgd.io.prefetch import Prefetcher
from tpu_sgd.io.sparse_wire import (ErrorFeedback, parse_wire_compress,
                                    plan_sparse_batches, stage_sparse_batch,
                                    topk_nnz, topk_select)
from tpu_sgd.io.wire import resolve_wire_dtype, wire_cast

#: default lookahead of every pipelined streaming path (double buffer)
DEFAULT_PREFETCH_DEPTH = 2

__all__ = [
    "Chunk",
    "ChunkPlan",
    "DEFAULT_PREFETCH_DEPTH",
    "ErrorFeedback",
    "Prefetcher",
    "pad_rows",
    "parse_wire_compress",
    "plan_chunks",
    "plan_sparse_batches",
    "resolve_wire_dtype",
    "stack_superchunk",
    "stage_sparse_batch",
    "topk_nnz",
    "topk_select",
    "wire_cast",
]
