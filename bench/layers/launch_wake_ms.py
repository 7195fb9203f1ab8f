"""Optimizer driver: per traced fit, the host's time from the start of
``train.dispatch`` to the end of ``train.fetch`` LESS the device's time in
the fit's longest launch (``sgd_run``, whole, over all the chips): two
durations, each on its own clock, so the session's clock offset is in
neither.  It is the runtime's launch and wake-up, the copy of the loss
history and, from host, the drain of the last blocks: everything between the
call and the answer during which the step's program does not run.  Mean over
the traced fits; None where no fit has both spans and a launch."""

from bench import host_share, spans


def read(trace: dict, run: dict):
    return host_share.part_ms(spans.of(trace, run), "wake", spans.find(run))
