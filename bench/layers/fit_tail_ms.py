"""Model harness: per traced fit, on the host's clock alone, from the end of
``train.fetch`` to the end of ``bench.fit``: ``fit.finish``, the entry's
``block_until_ready`` of the weights and the harness's read of them.  Mean
over the traced fits; None where no fit has a ``train.fetch`` span."""

from bench import host_share, spans


def read(trace: dict, run: dict):
    return host_share.part_ms(spans.of(trace, run), "tail")
