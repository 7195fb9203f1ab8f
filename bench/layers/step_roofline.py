"""Step: the least time the chip could take for one iteration — the bytes
the algorithm needs over the peak bytes/s, or its operations over the peak
operations/s, whichever is larger (``bench/work/<step>.py``'s ``least``,
``bench/peaks.json``) — over ``step_ms``.  The run's record says which bound
it is, and gives the same share for the bytes the program's layout moves."""

from bench.layers import step_ms


def least_ms(run: dict, which: str = "least"):
    work, peaks = run["work"][which], run["peaks"]
    by_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    by_flops = work["flops"] / peaks[run["work"]["flops_peak"]]
    return max(by_bytes, by_flops) * 1e3, \
        "bytes" if by_bytes >= by_flops else "operations"


def read(trace: dict, run: dict):
    measured = step_ms.read(trace, run)
    if not measured or run.get("peaks") is None:
        return None
    return 100.0 * least_ms(run)[0] / measured
