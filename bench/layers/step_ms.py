"""Step: the device's busy time inside ``bench.fit`` over the fit's
iterations.  Mean over the traced fits."""


def read(trace: dict, run: dict):
    fits = trace["fits"]
    if not fits or not trace["devices"]:
        return None
    busy = sum(f["busy_ns"] for f in fits) / len(fits)
    return busy / run["iterations"] / 1e6
