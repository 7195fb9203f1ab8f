"""Optimizer driver: program launches on the device's ``XLA Modules`` line
inside one ``bench.fit``.  Mean over the traced fits."""


def read(trace: dict, run: dict):
    fits = trace["fits"]
    if not fits or not trace["devices"]:
        return None
    return sum(f["programs"] for f in fits) / len(fits)
