"""Model harness: per traced fit, on the host's clock alone, the time inside
``bench.fit`` on its thread that no leaf span of the program covers (a span
with spans inside it covers only through them).  The program's leaves tile
its part of a fit, so what is left is the entry's and the harness's own:
the call down to the first leaf, and after the last the entry's
``block_until_ready`` of the weights and the harness's read of them
(``fit_tail_ms`` less ``fit.finish``).  Mean over the traced fits; None
where no fit has a ``train.run`` span."""

from bench import host_share, spans


def read(trace: dict, run: dict):
    reduced = spans.of(trace, run)
    if reduced is None:
        return None
    return host_share.mean_ms(map(host_share.unspanned_ns, reduced["fits"]))
