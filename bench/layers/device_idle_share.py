"""Device: 1 - the union of the device's operation intervals over the traced
fits' wall-clock (first fit's start to last fit's end)."""


def read(trace: dict, run: dict):
    if not trace["devices"] or not trace["window_ns"]:
        return None
    return 100.0 * (1.0 - trace["busy_ns"] / trace["window_ns"])
