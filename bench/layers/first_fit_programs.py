"""Model harness: the programs the first fit BUILDS: the ``build.compile``
spans of the last root that built something (``bench/first_fit.py``), each
with a lowering and a cache read of its own.  Not 1 even at the Optimizer
boundary: the one-operation programs on the way to the key and to ``w0`` are
built beside ``sgd_run``; from the host add the fill and the block writer, in
a stream the fold, the join and the run.  None on a program without the
record."""

from bench import first_fit


def read(trace: dict, run: dict):
    return first_fit.read("programs", run)
