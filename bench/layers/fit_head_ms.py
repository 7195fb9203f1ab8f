"""Model harness: per traced fit, on the host's clock alone, from the start
of ``bench.fit`` to the start of ``train.dispatch``: everything a fit does
before it calls its program (resident: the copy of the initial weights and
``train.select``; from host: validation, the plan and ``train.h2d``'s issue
of the blocks).  With ``launch_wake_ms``, the launch and ``fit_tail_ms`` it
is the fit (``bench/host_share.py``).  Mean over the traced fits; None where
no fit has a ``train.dispatch`` span."""

from bench import host_share, spans


def read(trace: dict, run: dict):
    return host_share.part_ms(spans.of(trace, run), "head")
