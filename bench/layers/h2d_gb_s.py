"""Model harness: the rate the dataset left the host at: the ``bytes`` of a
fit's ``train.h2d`` spans (what it was asked to move: X and y) over the
host's time inside them, in GB/s (bytes a nanosecond), mean over the traced
fits.  The host is in the span while it issues the blocks and the last of
them drain after it, so this reads a little over the wires' own rate; to be
read against 14.3 GB/s a wire (PERF.md section 4).  None where no fit has a
``train.h2d`` span that moved anything."""

from bench import spans


def read(trace: dict, run: dict):
    reduced = spans.of(trace, run)
    if reduced is None:
        return None
    rates = []
    for fit in reduced["fits"]:
        h2d = [s for s in fit["spans"] if s["name"] == "train.h2d"]
        moved = sum(float(s["stats"].get("bytes", 0)) for s in h2d)
        took = sum(s["end_ns"] - s["start_ns"] for s in h2d)
        if moved and took:
            rates.append(moved / took)
    if not rates:
        return None
    return sum(rates) / len(rates)
