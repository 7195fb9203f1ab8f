"""Optimizer driver: from the end of the fit's last operation on the device
to the end of ``train.fetch`` (``int(n_rec)`` and ``np.asarray(losses)``):
what fetching the loss history costs once the chip is done.  The span alone
would not say it: the host enters it while the program still runs.  Mean
over the traced fits."""

from bench import spans


def read(trace: dict, run: dict):
    reduced = spans.of(trace, run)
    if reduced is None:
        return None
    waits = []
    for fit in reduced["fits"]:
        ends = [s["end_ns"] for s in fit["spans"] if s["name"] == "train.fetch"]
        if ends and fit["last_op_end_ns"] is not None:
            waits.append(ends[-1] - fit["last_op_end_ns"])
    return sum(waits) / len(waits) / 1e6 if waits else None
