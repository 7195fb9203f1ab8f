"""Step: the hand-off's device work a FIT: own time of the operations whose
innermost ``sgd.*`` scope is ``sgd.stage`` (the fill of each destination and
the in-place write of each row block), mean over the chips and the traced
fits.  ``step_ms`` holds it in a cell that trains from the host (the device's
busy time inside a fit over its iterations): this is that part, by name.
None where no operation carries the scope (a dataset that is on the devices
already; a program from before the scope)."""

from bench import spans


def read(trace: dict, run: dict):
    reduced = spans.of(trace, run)
    if reduced is None or "sgd.stage" not in reduced["scopes"]:
        return None
    return reduced["scopes"]["sgd.stage"] / len(reduced["fits"]) / 1e6
