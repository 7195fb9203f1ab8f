"""Model harness: per traced fit, from the start of ``bench.fit`` to the
start of the first operation on the device inside it (validation,
``np.asarray``, the planner, the host-to-device copy).  Mean over the fits."""


def read(trace: dict, run: dict):
    waits = [f["first_op_ns"] - f["start_ns"] for f in trace["fits"]
             if f["first_op_ns"] is not None]
    return sum(waits) / len(waits) / 1e6 if waits else None
