"""Model harness: the first fit's TRACES: the union of the ``build.trace``
intervals of the last root that built something (``bench/first_fit.py``): the
Python of ``sgd_run`` and of every jitted function it calls, run once to make
the jaxpr.  What a trace kept across processes, or a configuration handed to
``sgd_run`` as operands, would take off ``setup_s`` (ROADMAP Speed 4).  On the
host's clock, taken inside set-up, tracing off.  None on a program without the
record."""

from bench import first_fit


def read(trace: dict, run: dict):
    return first_fit.read("trace_ms", run)
